"""Batched auction assignment with LP-dual admissible lower bounds.

The paper computes ``delta^BMa`` with the Hungarian algorithm — sequential
augmenting paths that do not map to a systolic machine.  The TPU-native
replacement (DESIGN.md §2) rests on two facts:

1. **Weak LP duality.**  For *any* price vector ``p``,

       dual(p) = sum_i min_j (c_ij + p_j) - sum_j p_j  <=  OPT(c),

   so a fixed number of auction sweeps yields a *valid* lower bound whose
   tightness is a dial (sweep count), never a correctness requirement.

2. **Forced-edge minors.**  ``OPT(c | row r -> col u) = c[r, u] + OPT(minor)``
   and the same ``p`` restricted to the minor is dual-feasible there, giving
   Alg. 3's "score every child with one solve" in O(N^2) total:

       forced_lb[u] = c[r, u] + sum_{i != r} min_{j != u} (c_ij + p_j)
                      - (sum_j p_j - p_u).

Sweeps are Jacobi (all unassigned rows bid in parallel): a row's bid is a
masked top-2 reduction — pure VPU work, batchable over thousands of search
states.  ``kernels/auction.py`` provides the fused Pallas version of one
sweep; this module is the reference/jnp implementation and the host of the
dual/forced-bound algebra.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

BIG = 1e7
# Cap on one bid increment.  Only a row whose second-best column is
# forbidden (a BIG entry of the GED cost matrices) bids more than any
# finite cost spread, and pricing a column ~BIG buys nothing: it only
# destroys the f32 arithmetic of the dual bounds (at ~1e7 the spacing is
# 1.0, so sum(prices) rounds and a forced bound can overshoot OPT — an
# inadmissible bound that prunes the optimal branch).  Capped, every price
# stays a small dyadic rational and the bounds are computed exactly; weak
# duality holds for any prices, so admissibility never depended on them.
MAX_BID = 1e3


class AuctionState(NamedTuple):
    prices: jnp.ndarray     # (..., N) float32 column prices
    row_to_col: jnp.ndarray  # (..., N) int32, -1 if unassigned
    col_to_row: jnp.ndarray  # (..., N) int32, -1 if unowned


def init_auction(cost: jnp.ndarray) -> AuctionState:
    shape = cost.shape[:-1]
    n = cost.shape[-1]
    return AuctionState(
        prices=jnp.zeros(shape, dtype=jnp.float32),
        row_to_col=jnp.full(shape[:-1] + (n,), -1, dtype=jnp.int32),
        col_to_row=jnp.full(shape[:-1] + (n,), -1, dtype=jnp.int32),
    )


def _top2_min(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(min, argmin, second-min) along the last axis."""
    m1 = jnp.min(x, axis=-1)
    a1 = jnp.argmin(x, axis=-1)
    masked = x + jax.nn.one_hot(a1, x.shape[-1], dtype=x.dtype) * BIG
    m2 = jnp.min(masked, axis=-1)
    return m1, a1, m2


def auction_sweep(cost: jnp.ndarray, st: AuctionState, eps: float) -> AuctionState:
    """One Jacobi sweep: every unassigned row bids; highest bid wins the col.

    ``cost``: (..., N, N).  Works for any leading batch dims.
    """
    n = cost.shape[-1]
    unassigned = st.row_to_col < 0                     # (..., N)
    m1, a1, m2 = kops.reduced_top2(cost, st.prices)    # fused kernel
    incr = jnp.minimum(m2 - m1, MAX_BID) + eps         # bid increment per row
    incr = jnp.where(unassigned, incr, -BIG)           # only unassigned bid

    # Resolve conflicts: per column, the bidding row with the largest
    # increment wins (one-hot scatter + argmax over rows).
    bid_onehot = jax.nn.one_hot(a1, n, dtype=cost.dtype)          # (..., N, N)
    bids = jnp.where(unassigned[..., None], bid_onehot * incr[..., None]
                     + (1.0 - bid_onehot) * (-BIG), -BIG)
    win_incr = jnp.max(bids, axis=-2)                 # (..., N) per col
    win_row = jnp.argmax(bids, axis=-2).astype(jnp.int32)
    has_bid = win_incr > -BIG / 2

    new_prices = jnp.where(has_bid, st.prices + win_incr, st.prices)

    # Ownership transfer: winning rows take their columns; displaced owners
    # become unassigned.
    old_owner = st.col_to_row
    new_col_to_row = jnp.where(has_bid, win_row, old_owner)
    # row_to_col: invert, preferring the new ownership map.
    cols = jnp.arange(n, dtype=jnp.int32)
    onehot_owner = (new_col_to_row[..., None, :]
                    == jnp.arange(n, dtype=jnp.int32)[..., :, None])  # (..., row, col)
    any_col = jnp.any(onehot_owner, axis=-1)
    new_row_to_col = jnp.where(
        any_col, jnp.argmax(onehot_owner, axis=-1).astype(jnp.int32), -1
    )
    del cols
    return AuctionState(new_prices, new_row_to_col, new_col_to_row)


def run_auction(cost: jnp.ndarray, n_sweeps: int, phases: Tuple[float, ...]
                = (1.0, 0.25, 0.125)) -> AuctionState:
    """Fixed-budget auction with epsilon-scaling.

    Standard forward-auction scaling: each phase halves eps, *unassigns all
    rows* and warm-starts from the previous phase's prices.  Without the
    reset the assignment freezes under coarse-phase price overshoot and the
    dual can stall arbitrarily far from OPT (observed in tests); with it the
    final phase's dual is within ~n*eps_final of OPT.
    """
    st = init_auction(cost)
    per_phase = max(n_sweeps // max(len(phases), 1), 1)

    for eps in phases:
        # phase reset: keep prices, drop the assignment
        st = AuctionState(st.prices,
                          jnp.full_like(st.row_to_col, -1),
                          jnp.full_like(st.col_to_row, -1))

        def body(_k, s, eps=eps):
            return auction_sweep(cost, s, eps)

        st = jax.lax.fori_loop(0, per_phase, body, st)
    return st


def dual_bound(cost: jnp.ndarray, prices: jnp.ndarray) -> jnp.ndarray:
    """Weak-duality lower bound on OPT(cost) for any price vector."""
    reduced = cost + prices[..., None, :]
    return jnp.sum(jnp.min(reduced, axis=-1), axis=-1) - jnp.sum(prices, axis=-1)


def forced_dual_bounds(cost: jnp.ndarray, prices: jnp.ndarray, row: jnp.ndarray
                       ) -> jnp.ndarray:
    """Lower bound on OPT(cost | row -> u) for **every** column u at once.

    ``row`` may be a scalar or a batch of per-problem row indices
    (shape = cost.shape[:-2]).  Returns (..., N).
    """
    n = cost.shape[-1]
    m1, a1, m2 = kops.reduced_top2(cost, prices)        # (..., N) per row
    # Row minima over columns != u: m2 where the argmin was u, else m1.
    u_ids = jnp.arange(n, dtype=jnp.int32)
    # (..., N rows, N u): rowmin excluding column u
    excl = jnp.where(a1[..., :, None] == u_ids, m2[..., :, None], m1[..., :, None])
    total_excl = jnp.sum(excl, axis=-2)                 # (..., N u)
    row_b = jnp.asarray(row, dtype=jnp.int32)
    row_excl = jnp.take_along_axis(
        excl, row_b[..., None, None].astype(jnp.int32), axis=-2
    )[..., 0, :]                                        # (..., N u)
    minors = total_excl - row_excl                      # sum_{i != row}
    p_tot = jnp.sum(prices, axis=-1, keepdims=True)
    c_row = jnp.take_along_axis(
        cost, row_b[..., None, None].astype(jnp.int32), axis=-2
    )[..., 0, :]
    return c_row + minors - (p_tot - prices)


def greedy_primal(cost: jnp.ndarray, prices: jnp.ndarray) -> jnp.ndarray:
    """A full (not necessarily optimal) assignment for upper-bound updates.

    Sequential greedy over rows on the reduced costs; O(N^2), fori_loop.
    Returns col index per row, shape (..., N).

    Prices are clipped before use: auction bids against forbidden (BIG)
    second-best columns legitimately inflate a price to ~BIG, which would
    invert the dummy/free class separation of the GED cost matrices and let
    a real vertex grab a PAD column.  Clipped price guidance keeps the
    near-optimal ordering where it matters (contested cheap columns) without
    ever overpowering the BIG structure.
    """
    n = cost.shape[-1]
    reduced = cost + jnp.clip(prices, 0.0, 1e3)[..., None, :]

    def body(i, carry):
        used, out = carry
        rowc = reduced[..., i, :] + jnp.where(used, BIG, 0.0)
        j = jnp.argmin(rowc, axis=-1).astype(jnp.int32)
        used = used | (jnp.arange(n, dtype=jnp.int32) == j[..., None])
        out = out.at[..., i].set(j)
        return used, out

    used0 = jnp.zeros(cost.shape[:-2] + (n,), dtype=bool)
    out0 = jnp.zeros(cost.shape[:-2] + (n,), dtype=jnp.int32)
    _, out = jax.lax.fori_loop(0, n, body, (used0, out0))
    return out
