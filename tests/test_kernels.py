"""Pallas kernel sweeps: shapes/dtypes vs the pure-jnp oracles in ref.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.bma_cost_matrix import bma_cost_matrix_pallas
from repro.kernels.lsa_children import lsa_children_pallas
from repro.kernels.merge_topk import merge_ranks_pallas
from repro.kernels.reduced_top2 import reduced_top2_pallas


def _bma_inputs(rng, b, n, le, vl=5, el=3):
    qv = jnp.asarray(rng.integers(0, vl, (b, n)), jnp.int32)
    gv = jnp.asarray(rng.integers(0, vl, (b, n)), jnp.int32)
    iq = jnp.asarray(rng.integers(0, 4, (b, n, le)), jnp.float32)
    ig = jnp.asarray(rng.integers(0, 4, (b, n, le)), jnp.float32)
    qa = jnp.asarray(rng.integers(0, el, (b, n, n)), jnp.int32)
    gc = jnp.asarray(rng.integers(0, el, (b, n, n)), jnp.int32)
    pa = jnp.asarray(rng.integers(0, 2, (b, n)), jnp.float32)
    return qv, gv, iq, ig, qa, gc, pa


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("n", [8, 16, 32, 64])
@pytest.mark.parametrize("le", [1, 2, 5])
def test_bma_cost_matrix_kernel_sweep(b, n, le):
    rng = np.random.default_rng(b * 100 + n + le)
    args = _bma_inputs(rng, b, n, le)
    got = bma_cost_matrix_pallas(*args, interpret=True)
    want = ref.bma_cost_matrix_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


@pytest.mark.parametrize("tile", [(8, 8), (16, 8), (8, 16)])
def test_bma_cost_matrix_kernel_tilings(tile):
    rng = np.random.default_rng(42)
    args = _bma_inputs(rng, 2, 32, 3)
    got = bma_cost_matrix_pallas(*args, tile_v=tile[0], tile_u=tile[1],
                                 interpret=True)
    want = ref.bma_cost_matrix_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b", [1, 4])
@pytest.mark.parametrize("n", [8, 16, 64, 128])
def test_reduced_top2_kernel_sweep(b, n):
    rng = np.random.default_rng(b * 7 + n)
    cost = jnp.asarray(rng.random((b, n, n)), jnp.float32)
    prices = jnp.asarray(rng.random((b, n)) * 3, jnp.float32)
    m1, a1, m2 = reduced_top2_pallas(cost, prices, interpret=True)
    w1, wa, w2 = ref.reduced_top2_ref(cost, prices)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(w1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(wa))
    np.testing.assert_allclose(np.asarray(m2), np.asarray(w2), rtol=1e-6)


def test_reduced_top2_with_big_entries():
    """BIG-masked (forbidden) entries must not confuse the top-2."""
    rng = np.random.default_rng(0)
    cost = rng.random((2, 16, 16)).astype(np.float32)
    cost[:, :, ::3] = 1e7
    cost = jnp.asarray(cost)
    prices = jnp.zeros((2, 16), jnp.float32)
    m1, a1, m2 = reduced_top2_pallas(cost, prices, interpret=True)
    w1, wa, w2 = ref.reduced_top2_ref(cost, prices)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(wa))


def test_ops_wrappers_vmap_and_grad_safety():
    """ops.* must work unbatched, batched, and under vmap."""
    rng = np.random.default_rng(1)
    b, n, le = 3, 16, 2
    qv, gv, iq, ig, qa, gc, pa = _bma_inputs(rng, b, n, le)
    ga = gc  # treat as adjacency; ops gathers internally
    img = jnp.asarray(rng.integers(0, n, (b, n)), jnp.int32)
    full = ops.bma_cost_matrix(qv, gv, iq, ig, qa, ga, img, pa)
    single = ops.bma_cost_matrix(qv[0], gv[0], iq[0], ig[0], qa[0], ga[0],
                                 img[0], pa[0])
    np.testing.assert_allclose(np.asarray(full[0]), np.asarray(single))
    vm = jax.vmap(ops.bma_cost_matrix)(qv, gv, iq, ig, qa, ga, img, pa)
    np.testing.assert_allclose(np.asarray(vm), np.asarray(full))


# ----------------------------------------------------------- LSa children

def _lsa_inputs(rng, b, n, le):
    """Random flat operands for the fused LSa kernel (see ref.py docs)."""
    f = lambda *s: jnp.asarray(rng.integers(0, 4, s), jnp.float32)
    return dict(
        base=jnp.asarray(rng.integers(0, 9, (b, n)) * 0.5, jnp.float32),
        free_g=jnp.asarray(rng.integers(0, 2, (b, n)), jnp.float32),
        rowhist_g=f(b, n, le),
        a_ju=jnp.asarray(rng.integers(0, le + 1, (b, n, n)), jnp.int32),
        qrow=jnp.asarray(rng.integers(0, le + 1, (b, n)), jnp.int32),
        pos_anch=jnp.asarray(rng.integers(0, 2, (b, n)), jnp.float32),
        cq=f(b, n, le), cg=f(b, n, le),
        base_j=f(b, n), adjb_j=f(b, n),
        hq_i=0.5 * f(b, le), hg_i=0.5 * f(b, le), cq_vi=f(b, le),
    )


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("n", [8, 16, 32, 64])
@pytest.mark.parametrize("le", [1, 2, 5])
def test_lsa_children_kernel_sweep(b, n, le):
    rng = np.random.default_rng(b * 1000 + n * 10 + le)
    args = _lsa_inputs(rng, b, n, le)
    got = lsa_children_pallas(*args.values(), interpret=True)
    want = ref.lsa_children_ref(*args.values())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tile_u", [8, 16, 32])
def test_lsa_children_kernel_tilings(tile_u):
    rng = np.random.default_rng(9)
    args = _lsa_inputs(rng, 2, 32, 3)
    got = lsa_children_pallas(*args.values(), tile_u=tile_u, interpret=True)
    want = ref.lsa_children_ref(*args.values())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lsa_ops_wrapper_unbatched_and_vmap():
    rng = np.random.default_rng(5)
    args = list(_lsa_inputs(rng, 3, 16, 2).values())
    full = ops.lsa_children(*args)
    single = ops.lsa_children(*(x[0] for x in args))
    np.testing.assert_array_equal(np.asarray(full[0]), np.asarray(single))
    vm = jax.vmap(ops.lsa_children)(*args)
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(full))


def _engine_state(rng, slots, n_graph, level):
    """A real (PairConsts, StateMasks, level, g_cost) engine state."""
    from repro.core.engine import bounds as eb
    from repro.core.engine.tensor_graphs import pack_pairs
    from repro.data.graphs import perturb, random_graph

    q = random_graph(rng, n_graph, density=0.4, n_vlabels=3, n_elabels=2)
    g = perturb(rng, q, int(rng.integers(0, 4)), n_vlabels=3, n_elabels=2)
    t = pack_pairs([(q, g)], slots=slots)
    pc = eb.make_pair_consts(
        jnp.asarray(t.qv[0]), jnp.asarray(t.gv[0]), jnp.asarray(t.qa[0]),
        jnp.asarray(t.ga[0]), jnp.asarray(t.order[0]), jnp.asarray(t.n[0]),
        t.n_vlabels, t.n_elabels)
    n = int(t.n[0])
    level = min(level, n - 1)
    img = np.full(slots, -1, np.int32)
    img[:level] = rng.permutation(n)[:level]
    sm = eb.state_masks(pc, jnp.asarray(img), jnp.int32(level))
    g_cost = jnp.float32(float(rng.integers(0, 7)) * 0.5)
    return pc, sm, jnp.int32(level), g_cost


@pytest.mark.parametrize("slots,n_graph,level",
                         [(8, 5, 0), (8, 8, 3), (16, 6, 1), (16, 12, 7),
                          (32, 9, 4)])
def test_lsa_engine_state_kernel_parity(slots, n_graph, level):
    """bounds.lsa_children kernel path == unfused path, bit for bit, on
    real engine states — PAD slots, bottom labels and masks included."""
    from repro.core.engine import bounds as eb
    rng = np.random.default_rng(slots * 100 + n_graph * 10 + level)
    pc, sm, lvl, g_cost = _engine_state(rng, slots, n_graph, level)
    want = eb.lsa_children(pc, sm, lvl, g_cost, use_kernel=False)
    got = eb.lsa_children(pc, sm, lvl, g_cost, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lsa_engine_state_kernel_parity_hypothesis():
    """Hypothesis sweep over graph sizes / levels / seeds (PAD-heavy slots
    included via the slots draw): the fused kernel must equal the unfused
    bound exactly — small-half float arithmetic leaves no rounding room."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.core.engine import bounds as eb

    @settings(max_examples=25, deadline=None)
    @given(slots=st.sampled_from([8, 16, 32]),
           n_graph=st.integers(3, 12),
           level=st.integers(0, 10),
           seed=st.integers(0, 2 ** 16))
    def check(slots, n_graph, level, seed):
        if n_graph > slots:
            n_graph = slots
        rng = np.random.default_rng(seed)
        pc, sm, lvl, g_cost = _engine_state(rng, slots, n_graph, level)
        want = eb.lsa_children(pc, sm, lvl, g_cost, use_kernel=False)
        got = eb.lsa_children(pc, sm, lvl, g_cost, use_kernel=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    check()


# -------------------------------------------------- merge-path rank counts

def _sorted_runs(rng, b, na, nb, lo=0, hi=6):
    """Key-sorted runs with plenty of ties (small integer keys)."""
    a = np.sort(rng.integers(lo, hi, (b, na)), axis=1).astype(np.float32)
    bb = np.sort(rng.integers(lo, hi, (b, nb)), axis=1).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(bb)


@pytest.mark.parametrize("b,na,nb", [(1, 8, 8), (3, 64, 32), (2, 128, 96),
                                     (1, 1016, 64), (2, 504, 128)])
def test_merge_ranks_kernel_sweep(b, na, nb):
    """Counts match the oracle AND numpy searchsorted on arbitrary run
    lengths (1016 and 504 exercise the padded last tile: 128-lane tiles
    over a run that is not a multiple of 128)."""
    rng = np.random.default_rng(b * 1000 + na + nb)
    ka, kb = _sorted_runs(rng, b, na, nb)
    ca, cb = merge_ranks_pallas(ka, kb, interpret=True)
    wa, wb = ref.merge_ranks_ref(ka, kb)
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(wb))
    for i in range(b):
        np.testing.assert_array_equal(
            np.asarray(ca[i]),
            np.searchsorted(np.asarray(kb[i]), np.asarray(ka[i]), "left"))
        np.testing.assert_array_equal(
            np.asarray(cb[i]),
            np.searchsorted(np.asarray(ka[i]), np.asarray(kb[i]), "right"))


@pytest.mark.parametrize("tile", [8, 16, 64])
def test_merge_ranks_kernel_tilings(tile):
    rng = np.random.default_rng(11)
    ka, kb = _sorted_runs(rng, 2, 128, 64)
    got = merge_ranks_pallas(ka, kb, tile_x=tile, interpret=True)
    want = ref.merge_ranks_ref(ka, kb)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_merge_ranks_all_ties_and_infs():
    """Degenerate runs: every key equal, and +inf PAD tails (the pool
    merge pads dead slots with +inf) — strict/non-strict must split them
    exactly as searchsorted left/right does."""
    ka = jnp.asarray([[2.0, 2.0, 2.0, 2.0, jnp.inf, jnp.inf, jnp.inf,
                       jnp.inf]], jnp.float32)
    kb = jnp.asarray([[2.0, 2.0, jnp.inf, jnp.inf, jnp.inf, jnp.inf,
                       jnp.inf, jnp.inf]], jnp.float32)
    ca, cb = merge_ranks_pallas(ka, kb, interpret=True)
    np.testing.assert_array_equal(np.asarray(ca)[0],
                                  [0, 0, 0, 0, 2, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(cb)[0],
                                  [4, 4, 8, 8, 8, 8, 8, 8])


def test_merge_ranks_ops_wrapper_unbatched():
    """ops.merge_ranks accepts unbatched (N,) runs and strips the batch
    axis back off; the ref path (REPRO_DISABLE_PALLAS) agrees."""
    rng = np.random.default_rng(5)
    ka, kb = _sorted_runs(rng, 1, 32, 16)
    ca2, cb2 = ops.merge_ranks(ka, kb)              # batched
    ca1, cb1 = ops.merge_ranks(ka[0], kb[0])        # unbatched
    assert ca1.shape == (32,) and cb1.shape == (16,)
    np.testing.assert_array_equal(np.asarray(ca1), np.asarray(ca2)[0])
    np.testing.assert_array_equal(np.asarray(cb1), np.asarray(cb2)[0])
    wa, wb = ref.merge_ranks_ref(ka, kb)
    np.testing.assert_array_equal(np.asarray(ca2), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(cb2), np.asarray(wb))
