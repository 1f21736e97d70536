"""Pallas kernel: fused delta^LSa child-bound vector (B, N).

The other half of the expansion hot path (the BMa half is
``bma_cost_matrix.py``): for every popped search state the engine scores
*all* children ``v_i -> u`` with the label-set anchor-aware bound — vertex
surplus, inner-edge histogram upsilons, per-anchor cross-term adjustments
and v_i's own cross component.

Unfused, the cross terms materialise a ``(pos, u, Le)`` one-hot ``aoh``
tensor plus half a dozen ``(N, N)``-shaped einsum intermediates per state,
each round-tripping HBM.  The kernel takes the *pre-reduced* histograms —
``(N, Le)``-sized contractions the engine computes with cheap matmuls —
and accumulates every per-``u`` reduction in VMEM, writing the single
``(B, N)`` bound vector once.

TPU mapping notes: the candidate axis ``u`` is tiled to the 128-lane VPU
axis and the anchor-position axis ``j`` runs down the sublanes, so every
tile is 2-D.  The wrapper lays each operand out so that the kernel body
only ever indexes a *leading* (untiled) block axis with a static label
``l`` — per-label rows ``(1, TU)``, per-label anchor columns ``(N, 1)``,
per-label scalars ``(1, 1)`` — and the edge-label loop is a static Python
loop (``Le`` is a compile-time shape).  Mosaic has no lowering for a
value-level slice at a traced index, and a block of 1 along a batch axis in
the last two dimensions breaks its (8, 128) tiling rule, so the batch axis
is a squeezed (``None``) block dimension in front.  Working set per grid
step, with double-buffered inputs and every ``(N, 1)`` column padded to
128 lanes: about 1.9 MiB at N = TU = 128, Le = 3, inside the 16 MiB of
scoped VMEM (see docs/kernels.md).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e7


def _kernel(base_ref, free_g_ref, rowhist_g_ref, a_ju_ref, qrow_ref,
            pa_ref, cq_ref, cg_ref, base_j_ref, adjb_j_ref, hq_i_ref,
            hg_i_ref, cq_vi_ref, out_ref):
    # Tile shapes: base/free_g/out (1, TU), rowhist_g (Le, 1, TU),
    # a_ju (N, TU), qrow/pa/base_j/adjb_j (N, 1), cq/cg (Le, N, 1),
    # hq_i/hg_i/cq_vi (Le, 1, 1).
    a_ju = a_ju_ref[...]
    tu = a_ju.shape[1]
    le = rowhist_g_ref.shape[0]

    zero_u = jnp.zeros((1, tu), jnp.float32)
    zero_1 = jnp.zeros((1, 1), jnp.float32)
    zero_nu = jnp.zeros(a_ju.shape, jnp.float32)
    inter_i = inter_vi = s2_u = zero_u
    n_i1 = sum_hg = s1_vi = zero_1
    cg_at = cq_at = zero_nu
    # one static pass over edge labels: inner edges, v_i's cross part, and
    # cg/cq gathered at each (j, u)'s edge label (the one-hot einsum of the
    # unfused path as an Le-step accumulation)
    for l in range(le):
        rgl = rowhist_g_ref[l]                            # (1, TU)
        hq, hg, cv = hq_i_ref[l], hg_i_ref[l], cq_vi_ref[l]   # (1, 1)
        inter_i = inter_i + jnp.minimum(hq, hg - rgl)
        inter_vi = inter_vi + jnp.minimum(cv, rgl)
        s2_u = s2_u + rgl
        n_i1, sum_hg, s1_vi = n_i1 + hq, sum_hg + hg, s1_vi + cv
        m = (a_ju == l + 1).astype(jnp.float32)          # (N, TU)
        cg_at = cg_at + m * cg_ref[l]
        cq_at = cq_at + m * cq_ref[l]
    ups_i = jnp.maximum(n_i1, sum_hg - s2_u) - inter_i
    ups_vi = jnp.maximum(s1_vi, s2_u) - inter_vi

    pa = pa_ref[...]                                      # (N, 1)
    d_ju = (cg_at <= cq_at).astype(jnp.float32)
    ups_ju = jnp.where(a_ju > 0, adjb_j_ref[...] + d_ju, base_j_ref[...])
    cross = jnp.sum(ups_ju * pa, axis=0, keepdims=True)  # (1, TU)

    # exact-delta edge mismatches of (v_i -> u)
    mism = (qrow_ref[...] != a_ju).astype(jnp.float32)
    de = jnp.sum(mism * pa, axis=0, keepdims=True)

    lb = base_ref[...] + de + ups_i + ups_vi + cross
    out_ref[...] = jnp.where(free_g_ref[...] > 0, lb, BIG)


@functools.partial(jax.jit, static_argnames=("tile_u", "interpret"))
def lsa_children_pallas(
    base: jnp.ndarray,       # (B, N) f32
    free_g: jnp.ndarray,     # (B, N) f32
    rowhist_g: jnp.ndarray,  # (B, N, Le) f32
    a_ju: jnp.ndarray,       # (B, N, N) int32
    qrow: jnp.ndarray,       # (B, N) int32
    pos_anch: jnp.ndarray,   # (B, N) f32
    cq: jnp.ndarray,         # (B, N, Le) f32
    cg: jnp.ndarray,         # (B, N, Le) f32
    base_j: jnp.ndarray,     # (B, N) f32
    adjb_j: jnp.ndarray,     # (B, N) f32
    hq_i: jnp.ndarray,       # (B, Le) f32
    hg_i: jnp.ndarray,       # (B, Le) f32
    cq_vi: jnp.ndarray,      # (B, Le) f32
    tile_u: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    b, n = base.shape
    if rowhist_g.shape[-1] == 0:
        # no edge labels: one all-zero label contributes nothing to any
        # histogram term, and keeps every block non-empty
        rowhist_g, cq, cg = (jnp.zeros((b, n, 1), jnp.float32),) * 3
        hq_i = hg_i = cq_vi = jnp.zeros((b, 1), jnp.float32)
    le = rowhist_g.shape[-1]
    # default tile: the whole candidate axis up to the 128 VPU lanes (a
    # block equal to the array extent always meets Mosaic's tiling rule),
    # past that the largest power-of-two divisor of n up to 128, so pinned
    # odd slot counts still trace (an explicit tile_u must divide n)
    tu = tile_u or (n if n <= 128 else math.gcd(n, 128))
    assert n % tu == 0, (n, tu)

    row = lambda x: x.reshape(b, 1, n)                   # (B, 1, N)
    col = lambda x: x.reshape(b, n, 1)                   # (B, N, 1)
    per_label_col = lambda x: jnp.swapaxes(x, 1, 2)[..., None]  # (B, Le, N, 1)
    per_label_1 = lambda x: x.reshape(b, le, 1, 1)       # (B, Le, 1, 1)

    tile = pl.BlockSpec((None, 1, tu), lambda bb, j: (bb, 0, j))
    full_col = pl.BlockSpec((None, n, 1), lambda bb, j: (bb, 0, 0))
    label_col = pl.BlockSpec((None, le, n, 1), lambda bb, j: (bb, 0, 0, 0))
    label_1 = pl.BlockSpec((None, le, 1, 1), lambda bb, j: (bb, 0, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(b, n // tu),
        in_specs=[
            tile,                                                    # base
            tile,                                                    # free_g
            pl.BlockSpec((None, le, 1, tu), lambda bb, j: (bb, 0, 0, j)),
            pl.BlockSpec((None, n, tu), lambda bb, j: (bb, 0, j)),   # a_ju
            full_col,                                                # qrow
            full_col,                                                # pos_anch
            label_col,                                               # cq
            label_col,                                               # cg
            full_col,                                                # base_j
            full_col,                                                # adjb_j
            label_1,                                                 # hq_i
            label_1,                                                 # hg_i
            label_1,                                                 # cq_vi
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        interpret=interpret,
        name="lsa_children",
    )(row(base), row(free_g), jnp.swapaxes(rowhist_g, 1, 2)[:, :, None, :],
      a_ju, col(qrow), col(pos_anch), per_label_col(cq), per_label_col(cg),
      col(base_j), col(adjb_j), per_label_1(hq_i), per_label_1(hg_i),
      per_label_1(cq_vi))
    return out.reshape(b, n)
