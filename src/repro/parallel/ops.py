"""SPMD-friendly op variants.

``jax.lax.top_k`` lowers to a TopK custom-call that the SPMD partitioner
treats as opaque: every operand is ALL-GATHERED to full global shape first.
Measured on the ged-verify dry-run cell (32768 pairs, when top_k still ran
inside the search loop): 494 TB of all-gather traffic per device — 98% of
the cell's collective bytes — for an op that is mathematically per-row.

``top_k_sorted`` uses a variadic sort + gather instead: ``sort`` HLO is
batch-partitionable, and the gather carries explicit batch dims, so the
batch dimension stays sharded.  The MoE router still pops through it; the
GED search loop no longer needs *any* per-iteration pool-sized sort — its
pool is kept key-sorted, pop is a slice, and :func:`merge_sorted_topk`
(below) folds freshly sorted children in with two binary-search rank
passes (see ``core/engine/search.py`` and ``docs/kernels.md``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp


def shard_map(f: Optional[Callable] = None, *, mesh, in_specs, out_specs,
              check: bool = False):
    """``jax.shard_map`` with its replication check (``check_vma``) off by
    default; usable bare or as a decorator factory."""
    import jax

    def wrap(fn: Callable) -> Callable:
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check)

    return wrap if f is None else wrap(f)


def top_k_sorted(x: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Largest-k along the last axis. Drop-in for ``jax.lax.top_k``.

    One variadic ``lax.sort`` carrying (keys, iota) — no gather in the
    forward, vmap/SPMD transparent.  NOTE: this jaxlib's sort *transpose*
    (like its batched-gather transpose) is broken, so don't differentiate
    through the returned values; the MoE router instead takes
    ``stop_gradient`` ids and re-reads weights via a one-hot einsum
    (``models/moe.py``) — gradient-correct and gather-free.
    """
    import jax
    n = x.shape[-1]
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), x.shape)
    neg_sorted, order = jax.lax.sort((-x, idx), num_keys=1, dimension=-1)
    return -neg_sorted[..., :k], order[..., :k]


def sort_by_key(keys: jnp.ndarray, payload: Any
                ) -> Tuple[jnp.ndarray, Any]:
    """Stable ascending sort of ``keys`` (1-D) carrying a payload pytree.

    The permutation comes from one variadic ``lax.sort`` over
    ``(keys, iota)`` — stable (equal keys keep their input order),
    batch-partitionable, and gather-free in the key pass; payload leaves
    (any trailing shape, leading axis = ``len(keys)``) are gathered once.
    """
    import jax
    n = keys.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    keys_sorted, order = jax.lax.sort((keys, iota), num_keys=1, dimension=-1)
    return keys_sorted, jax.tree.map(lambda x: x[order], payload)


def merge_sorted_topk(
    keys_a: jnp.ndarray,
    keys_b: jnp.ndarray,
    payload_a: Any,
    payload_b: Any,
    keep: int,
    drop_a: Optional[jnp.ndarray] = None,
    drop_b: Optional[jnp.ndarray] = None,
    perm_b: Optional[jnp.ndarray] = None,
    use_kernel: bool = False,
) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Merge two key-sorted runs, keep the smallest ``keep``, no argsort.

    The search loop's frontier-maintenance primitive (the sorted-pool
    invariant): run A is the surviving pool — already sorted from the
    previous merge — and run B is the freshly sorted child batch.  Rather
    than re-sorting all ``len(A) + len(B)`` keys every iteration, each
    element's merged rank is its own index plus its binary-search position
    in the *other* run (the merge-path rank trick):

        rank_a[i] = i + |{j : keys_b[j] <  keys_a[i]}|   (ties: A first)
        rank_b[j] = j + |{i : keys_a[i] <= keys_b[j]}|

    which is a stable merge — identical ordering to a stable sort of
    ``concat(A, B)`` — at ``O((|A|+|B|) log)`` binary-search cost instead
    of a full ``O((|A|+|B|) log(|A|+|B|))`` sort network.  Elements with
    rank >= ``keep`` are dropped; the returned scalar is the minimum of
    their ``drop_*`` values (``+inf`` when nothing was dropped), which is
    how the engine tracks the dropped-lower-bound floor its exactness
    certificate depends on.

    ``payload_*`` are pytrees of arrays with leading axis matching their
    run's keys.  Payload rows move through one *gather* from the
    concatenated runs via a scalar source-index map — XLA lowers row
    gathers far better than row scatters (2x on the CPU backend at pool
    shapes, see the ``kernel_hotpath`` bench) and the scalar scatters
    building the map are cheap.  ``perm_b`` composes a preceding key sort
    into that map: pass ``payload_b`` (and ``drop_b``) in *pre-sort* row
    order together with the sort permutation (sorted position ``j`` came
    from row ``perm_b[j]``), and the payload skips its own sort-time
    gather entirely — the engine sorts only child *keys*.

    1-D keys only — the engine ``vmap``s this over pairs.  ``keep`` must
    not exceed ``len(A) + len(B)`` (short runs would leave zero-filled
    output rows).

    ``use_kernel=True`` computes the two rank-count passes with the
    Pallas comparison-matrix kernel (``kernels/merge_topk.py``) instead
    of binary searches — same integer ranks (the kernel counts exactly
    the searchsorted left/right semantics), so the output is
    bit-identical; everything downstream (scatters, payload gather,
    floor) is shared.
    """
    import jax
    na, nb = keys_a.shape[0], keys_b.shape[0]

    if use_kernel:
        from repro.kernels import ops as kops
        count_a, count_b = kops.merge_ranks(keys_a, keys_b)
        rank_a = jnp.arange(na, dtype=jnp.int32) + count_a
        rank_b = jnp.arange(nb, dtype=jnp.int32) + count_b
    else:
        def rank_in(run, values, side):
            # unrolled binary search for short runs: log2(n) fused gather
            # steps beat the rolled scan's loop-carry overhead inside the
            # engine's while_loop; the rolled form wins on big runs
            method = "scan_unrolled" if run.shape[0] <= 256 else "scan"
            return jnp.searchsorted(run, values, side=side,
                                    method=method).astype(jnp.int32)

        rank_a = jnp.arange(na, dtype=jnp.int32) + rank_in(keys_b, keys_a,
                                                           "left")
        rank_b = jnp.arange(nb, dtype=jnp.int32) + rank_in(keys_a, keys_b,
                                                           "right")

    # keys land via (cheap) scalar scatters; payload rows via one gather
    keys_out = jnp.zeros((keep,), keys_a.dtype)
    keys_out = keys_out.at[rank_a].set(keys_a, mode="drop")
    keys_out = keys_out.at[rank_b].set(keys_b, mode="drop")

    row_b = jnp.arange(nb, dtype=jnp.int32) if perm_b is None \
        else perm_b.astype(jnp.int32)
    src = jnp.zeros((keep,), jnp.int32)
    src = src.at[rank_a].set(jnp.arange(na, dtype=jnp.int32), mode="drop")
    src = src.at[rank_b].set(na + row_b, mode="drop")
    payload_out = jax.tree.map(
        lambda xa, xb: jnp.concatenate([xa, xb], axis=0)[src],
        payload_a, payload_b)

    if drop_a is None:
        drop_a = keys_a
    if drop_b is None:
        drop_b = keys_b
    elif perm_b is not None:
        drop_b = drop_b[row_b]              # re-align with the sorted keys
    inf = jnp.asarray(jnp.inf, drop_a.dtype)
    dropped_min = jnp.minimum(
        jnp.min(jnp.where(rank_a >= keep, drop_a, inf), initial=jnp.inf),
        jnp.min(jnp.where(rank_b >= keep, drop_b, inf), initial=jnp.inf),
    ).astype(drop_a.dtype)
    return keys_out, payload_out, dropped_min
