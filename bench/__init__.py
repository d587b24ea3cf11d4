"""The chip benchmark of the simulator: see bench/README.md."""
