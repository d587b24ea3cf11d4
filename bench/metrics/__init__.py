"""One reader per metric: ``read(record) -> float | None``.

``record`` holds the run's window points (``points``: one dict per point with
``events``, ``windows``, ``fallback``, ``build_s``, ``run_s``,
``readback_s``, ``trace_lower_s``, ``compile_load_s``), ``window_s``,
``setup_s``, ``peak_bytes`` and, in a traced run, ``trace`` (the reduction of
``bench/trace.py``). A reader that finds nothing to read returns None and the
metric is left out of the result line.
"""
