"""Max–min fair bandwidth sharing (progressive filling) as a Pallas kernel.

The paper's interrupt-based traffic model recomputes every flow's fair share on
each flow start/end — the per-event hot spot of the network component (§4.2, the
Fig-2 event storm). The fixed point is computed by at most L water-filling rounds;
each round is two (L,F)x(F,) matvecs + reductions, all VMEM-resident. Mirrors
core.network.maxmin_rates in f32, but not bit for bit: the sums run in another
order, so rates differ by a few ulps (largest relative difference 6.4e-7 over
175 random cases in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-6
_BIG = 3.0e38


def _waterfill_kernel(inc_ref, bw_ref, act_ref, rate_ref, *, n_links: int):
    """Flows run down the sublanes, links across the lanes: per-flow vectors
    are (F, 1) columns, per-link vectors (1, L) rows, and each matvec is a
    broadcast multiply plus a sum over one axis (VPU only, no MXU layout)."""
    active = act_ref[...]                   # (F, 1) f32 0/1
    inc = inc_ref[...] * active             # (F, L)
    bw = bw_ref[...]                        # (1, L)

    def round_(_, carry):
        rate, frozen = carry                # (F, 1), (F, 1) f32
        unfrozen = active * (1.0 - frozen)
        n_unf = jnp.sum(inc * unfrozen, axis=0, keepdims=True)
        used = jnp.sum(inc * (rate * frozen), axis=0, keepdims=True)
        resid = jnp.maximum(bw - used, 0.0)
        fair = jnp.where(n_unf > 0, resid / jnp.maximum(n_unf, 1.0), _BIG)
        fair = jnp.where((bw <= 0) & (n_unf > 0), 0.0, fair)
        level = jnp.min(fair, axis=1, keepdims=True)           # (1, 1)
        bottleneck = (fair <= level + _EPS).astype(jnp.float32)
        hits = jnp.sum(inc * bottleneck, axis=1, keepdims=True) > 0
        newly = unfrozen * hits.astype(jnp.float32)
        rate = jnp.where(newly > 0, level, rate)
        frozen = jnp.maximum(frozen, newly)
        return rate, frozen

    rate0 = jnp.zeros(active.shape, jnp.float32)
    rate, _ = jax.lax.fori_loop(0, n_links, round_, (rate0, 1.0 - active))
    rate_ref[...] = jnp.where(active > 0, rate, 0.0)


def maxmin_rates_pallas(inc: jax.Array, bw: jax.Array, active: jax.Array, *,
                        interpret=False) -> jax.Array:
    """inc: (F, L) 0/1 f32; bw: (L,); active: (F,) bool -> (F,) f32 rates."""
    f, l = inc.shape
    return pl.pallas_call(
        functools.partial(_waterfill_kernel, n_links=l),
        out_shape=jax.ShapeDtypeStruct((f, 1), jnp.float32),
        interpret=interpret,
    )(inc.astype(jnp.float32), bw.astype(jnp.float32)[None],
      active.astype(jnp.float32)[:, None])[:, 0]
