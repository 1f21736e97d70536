"""Pallas kernel: fused lambda^BMa branch-cost matrix (B, N, N).

The hottest op of the batched GED engine: for every expanded search state the
engine needs the full pairwise branch-edit cost matrix

    lam[v, u] = 1[l(v) != l(u)]
                + 1/2 * Y(inner-edge hists of v and u)
                + sum_{anchored j} 1[qa[v, order_j] != ga[u, img_j]]

Unfused, this is three (N, N)-shaped intermediates (vertex mismatch, pairwise
histogram Y, anchor mismatch counts) each round-tripping HBM.  The kernel
tiles (v, u) into VMEM blocks and accumulates the label- and anchor-
reductions with on-chip loops, writing ``lam`` once.

TPU mapping notes: the (TV, TU) tile is VPU-aligned (lanes = 128 on the u
axis, sublanes on v) and the batch axis is a squeezed (``None``) block
dimension, so the (8, 128) tiling rule only ever sees full or aligned tile
extents.  Mosaic cannot slice a value at a traced index, so the wrapper lays
the operands out for the loops: v-side per-label histograms as ``(Le, TV,
1)`` columns and u-side ones as ``(Le, 1, TU)`` rows (indexed by a static
label on the leading axis), and the gathered g adjacency transposed to
``(N, TU)`` so the anchor loop loads row ``j`` with a dynamic sublane
slice.  The matching q column is a masked lane reduction of the ``(TV, N)``
tile.  Working set per step: O(TV*N + TU*N) int32 + O(TV*TU) f32 plus the
lane-padded label columns — about 1.4 MiB at N = 128, Le = 3 with
double-buffered inputs, inside the 16 MiB of scoped VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(qv_ref, gv_ref, iq_ref, ig_ref, qa_ref, gct_ref, pa_ref,
            out_ref):
    # Tile shapes: qv (TV, 1), gv (1, TU), iq (Le, TV, 1), ig (Le, 1, TU),
    # qa (TV, N), gct (N, TU), pa (N, 1) -> out (TV, TU).
    qa = qa_ref[...]
    tv, n = qa.shape
    tu = gct_ref.shape[1]
    le = iq_ref.shape[0]

    vmis = (qv_ref[...] != gv_ref[...]).astype(jnp.float32)

    sq = jnp.zeros((tv, 1), jnp.float32)
    sg = jnp.zeros((1, tu), jnp.float32)
    inter = jnp.zeros((tv, tu), jnp.float32)
    for l in range(le):
        iql, igl = iq_ref[l], ig_ref[l]
        sq, sg = sq + iql, sg + igl
        inter = inter + jnp.minimum(iql, igl)
    ups = jnp.maximum(sq, sg) - inter

    lanes = jax.lax.broadcasted_iota(jnp.int32, qa.shape, 1)
    zero_u = jnp.zeros((1, tu), jnp.float32)

    def anchor_body(j, acc):
        q_col = jnp.sum(jnp.where(lanes == j, qa, 0), axis=1, keepdims=True)
        g_row = gct_ref[pl.ds(j, 1), :]                   # (1, TU)
        # widen pa[j] along lanes first: Mosaic cannot broadcast a (1, 1)
        # value over sublanes and lanes in one step
        p_row = zero_u + pa_ref[pl.ds(j, 1), :]           # (1, TU)
        return acc + jnp.where(q_col != g_row, p_row, 0.0)

    mism = jax.lax.fori_loop(0, n, anchor_body,
                             jnp.zeros((tv, tu), dtype=jnp.float32))

    out_ref[...] = vmis + 0.5 * ups + mism


@functools.partial(jax.jit, static_argnames=("tile_v", "tile_u", "interpret"))
def bma_cost_matrix_pallas(
    qv: jnp.ndarray,        # (B, N) int32
    gv: jnp.ndarray,        # (B, N) int32
    inner_q: jnp.ndarray,   # (B, N, Le) f32
    inner_g: jnp.ndarray,   # (B, N, Le) f32
    qa_ord: jnp.ndarray,    # (B, N, N) int32
    gcross: jnp.ndarray,    # (B, N, N) int32
    pos_anch: jnp.ndarray,  # (B, N) f32
    tile_v: int = 0,
    tile_u: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    b, n = qv.shape
    if inner_q.shape[-1] == 0:
        # no edge labels: one all-zero label leaves Y unchanged and keeps
        # every block non-empty
        inner_q = inner_g = jnp.zeros((b, n, 1), jnp.float32)
    le = inner_q.shape[-1]
    tv = tile_v or min(n, 128)
    tu = tile_u or min(n, 128)
    assert n % tv == 0 and n % tu == 0, (n, tv, tu)
    grid = (b, n // tv, n // tu)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tv, 1), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, 1, tu), lambda bb, i, j: (bb, 0, j)),
            pl.BlockSpec((None, le, tv, 1), lambda bb, i, j: (bb, 0, i, 0)),
            pl.BlockSpec((None, le, 1, tu), lambda bb, i, j: (bb, 0, 0, j)),
            pl.BlockSpec((None, tv, n), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, n, tu), lambda bb, i, j: (bb, 0, j)),
            pl.BlockSpec((None, n, 1), lambda bb, i, j: (bb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tv, tu), lambda bb, i, j: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n, n), jnp.float32),
        interpret=interpret,
        name="bma_cost_matrix",
    )(qv.reshape(b, n, 1), gv.reshape(b, 1, n),
      jnp.swapaxes(inner_q, 1, 2)[..., None],
      jnp.swapaxes(inner_g, 1, 2)[:, :, None, :], qa_ord,
      jnp.swapaxes(gcross, 1, 2), pos_anch.reshape(b, n, 1))
