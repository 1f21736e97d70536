"""Pallas kernel: merge-path rank counts for the sorted-pool merge.

``parallel/ops.merge_sorted_topk`` merges two key-sorted runs by computing,
for every element, its rank in the merged order:

    rank_a[i] = i + #{j : keys_b[j] <  keys_a[i]}     (searchsorted "left")
    rank_b[j] = j + #{i : keys_a[i] <= keys_b[j]}     (searchsorted "right")

The binary searches are latency-bound on the VPU (log2(N) dependent gather
steps per element).  Because both runs are already sorted *and* small
enough to sit in VMEM whole (a (2048,) f32 run is 8 KiB against the
~16 MiB budget), the counts can instead be computed as a dense tiled
comparison-matrix reduction — pure vectorised compares + an add-reduce,
no gathers, one output write per element.  The integer counts are exactly
the searchsorted semantics, so the downstream merge (scatters, payload
gather, dropped-lb floor) is bit-identical.

One generic kernel handles both directions: ``count[x_i] = #{y_j R x_i}``
with the comparison ``R`` (strict ``<`` vs ``<=``) a static flag.  The x
run is tiled over the grid; the full y run rides along in every grid step
(revisited blocks are read-only, which Mosaic allows at any grid
position).  Working set per step: the (TX,) x tile, the (NY,) y run and
the (TX, NY) comparison tile — about 1 MiB at TX = 128, NY = 2048.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, y_ref, out_ref, *, strict):
    # Tile shapes: x (1, TX), y (1, NY) -> out (1, TX).
    x = x_ref[0]                # (TX,)
    y = y_ref[0]                # (NY,)
    if strict:
        cmp = y[None, :] < x[:, None]      # (TX, NY)
    else:
        cmp = y[None, :] <= x[:, None]
    out_ref[0] = jnp.sum(cmp.astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("strict", "tile_x", "interpret"))
def rank_counts_pallas(x, y, *, strict=True, tile_x=0, interpret=False):
    """count[b, i] = #{j : y[b, j] R x[b, i]}, R = ``<`` (strict) or ``<=``.

    ``x``/``y`` are key-sorted runs (B, NX)/(B, NY) f32; sortedness is not
    required for correctness here (the counts are plain comparison sums)
    but is what makes the counts equal to searchsorted ranks downstream.
    """
    b, n_real = x.shape
    ny = y.shape[-1]
    # Mosaic's (8, 128) tiling rule: x tiles are 128 lanes (or the whole
    # run when shorter), a run that is not a multiple of the tile (the pool
    # remainder P - B) is padded and the padded counts dropped, and the
    # runs travel as (B, 1, N) rows under a squeezed batch block dimension
    tx = tile_x or min(n_real, 128)
    x = jnp.pad(x, ((0, 0), (0, -n_real % tx)))
    nx = x.shape[1]
    grid = (b, nx // tx)
    kern = functools.partial(_kernel, strict=strict)
    x_tile = pl.BlockSpec((None, 1, tx), lambda bb, i: (bb, 0, i))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            x_tile,
            pl.BlockSpec((None, 1, ny), lambda bb, i: (bb, 0, 0)),
        ],
        out_specs=x_tile,
        out_shape=jax.ShapeDtypeStruct((b, 1, nx), jnp.int32),
        interpret=interpret,
        name="merge_rank_counts",
    )(x.reshape(b, 1, nx), y.reshape(b, 1, ny))
    return out.reshape(b, nx)[:, :n_real]


def merge_ranks_pallas(keys_a, keys_b, *, tile_x=0, interpret=False):
    """Both rank-count vectors for a two-run merge: (count_a, count_b).

    count_a[i] = #{j : keys_b[j] <  keys_a[i]}   (int32, (B, NA))
    count_b[j] = #{i : keys_a[i] <= keys_b[j]}   (int32, (B, NB))

    Two launches of the generic kernel rather than one two-output kernel:
    the two outputs tile over *different* axes, and a fused variant would
    have to revisit one of them across non-consecutive grid steps, which
    the TPU output-revisiting rule forbids.
    """
    count_a = rank_counts_pallas(keys_a, keys_b, strict=True,
                                 tile_x=tile_x, interpret=interpret)
    count_b = rank_counts_pallas(keys_b, keys_a, strict=False,
                                 tile_x=tile_x, interpret=interpret)
    return count_a, count_b
