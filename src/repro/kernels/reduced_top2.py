"""Pallas kernel: per-row (min, argmin, second-min) of ``cost + prices``.

The inner op of both the auction sweep (bid computation) and the forced
dual bounds (minor row minima).  Fusing the price broadcast with the double
reduction avoids materialising the reduced (B, N, N) matrix in HBM twice.

Tiling: rows (bidders) tiled to ``TR`` sublanes; the full column axis (N <=
512) stays resident in VMEM lanes, so each grid step is one VMEM-local
top-2 reduction.  The batch axis is a squeezed (``None``) block dimension
and the per-row vectors travel as ``(B, 1, N)``, so no block has a 1 along
a batch axis in its last two dimensions (Mosaic's (8, 128) tiling rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e7


def _kernel(cost_ref, prices_ref, m1_ref, a1_ref, m2_ref):
    # Tile shapes: cost (TR, N), prices (1, N) -> m1/a1/m2 (1, TR).
    cost = cost_ref[...]        # (TR, N)
    prices = prices_ref[0]      # (N,)
    red = cost + prices[None, :]
    m1 = jnp.min(red, axis=1)
    a1 = jnp.argmin(red, axis=1).astype(jnp.int32)
    n = red.shape[1]
    onehot = (jnp.arange(n, dtype=jnp.int32)[None, :] == a1[:, None])
    m2 = jnp.min(red + onehot.astype(red.dtype) * BIG, axis=1)
    m1_ref[0] = m1
    a1_ref[0] = a1
    m2_ref[0] = m2


@functools.partial(jax.jit, static_argnames=("tile_r", "interpret"))
def reduced_top2_pallas(
    cost: jnp.ndarray,      # (B, N, N) f32
    prices: jnp.ndarray,    # (B, N) f32
    tile_r: int = 0,
    interpret: bool = False,
):
    b, n, _ = cost.shape
    tr = tile_r or min(n, 128)
    assert n % tr == 0
    grid = (b, n // tr)
    out_shapes = (
        jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
        jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
    )
    row = pl.BlockSpec((None, 1, tr), lambda bb, i: (bb, 0, i))
    m1, a1, m2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tr, n), lambda bb, i: (bb, i, 0)),
            pl.BlockSpec((None, 1, n), lambda bb, i: (bb, 0, 0)),
        ],
        out_specs=(row, row, row),
        out_shape=out_shapes,
        interpret=interpret,
        name="reduced_top2",
    )(cost, prices.reshape(b, 1, n))
    return m1.reshape(b, n), a1.reshape(b, n), m2.reshape(b, n)
