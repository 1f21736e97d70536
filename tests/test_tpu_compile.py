"""The Pallas kernels and a kernel-enabled search step compile for a TPU v5e.

Nothing here runs on a chip: each test compiles for one chip of a
*described* ``v5e:2x2`` topology, which the TPU compiler that ships with
jaxlib accepts without an attached device.  That catches what interpret
mode cannot — Mosaic's (8, 128) tiling rule, primitives it cannot lower,
VMEM limits — at no chip time.  The topology is described inside a
fixture (never at import), so pytest workers that are not given this file
never load the TPU library.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.autotune import KernelDispatch
from repro.kernels.bma_cost_matrix import bma_cost_matrix_pallas
from repro.kernels.lsa_children import lsa_children_pallas
from repro.kernels.merge_topk import merge_ranks_pallas
from repro.kernels.reduced_top2 import reduced_top2_pallas

LE = 3          # AIDS edge labels
STATES = 8      # expanded states per pair (EngineConfig.expand)
POOL = 1024     # frontier pool (EngineConfig.pool)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """Compile for the described chip: the kernels lower with Mosaic (not
    interpret mode) and the persistent compile cache is off, since an
    executable compiled without a chip cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def compile_(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    monkeypatch.undo()
    jax.clear_caches()          # no trace with interpret=False may leak
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]


def _kernel_args(kernel, n, lead):
    """(fn, shape specs) for one kernel: ``lead`` is the leading batch
    shape — the vmapped engine form passes unbatched operands per state
    (the ops wrappers add the size-1 batch), the batched form ``(B,)``."""
    f32, i32 = jnp.float32, jnp.int32
    if kernel == "lsa":
        spec = [(lead + (n,), f32), (lead + (n,), f32), (lead + (n, LE), f32),
                (lead + (n, n), i32), (lead + (n,), i32), (lead + (n,), f32),
                (lead + (n, LE), f32), (lead + (n, LE), f32),
                (lead + (n,), f32), (lead + (n,), f32), (lead + (LE,), f32),
                (lead + (LE,), f32), (lead + (LE,), f32)]
        return ops.lsa_children, lsa_children_pallas, spec
    if kernel == "bma":
        engine = [(lead + (n,), i32), (lead + (n,), i32),
                  (lead + (n, LE), f32), (lead + (n, LE), f32),
                  (lead + (n, n), i32), (lead + (n, n), i32),
                  (lead + (n,), i32), (lead + (n,), f32)]
        batched = engine[:5] + [(lead + (n, n), i32), (lead + (n,), f32)]
        return ops.bma_cost_matrix, bma_cost_matrix_pallas, (engine, batched)
    if kernel == "top2":
        spec = [(lead + (n, n), f32), (lead + (n,), f32)]
        return ops.reduced_top2, reduced_top2_pallas, spec
    # merge: the unpopped pool remainder against the sorted children
    spec = [(lead + (POOL - STATES,), f32), (lead + (STATES * n,), f32)]
    return ops.merge_ranks, merge_ranks_pallas, spec


KERNELS = ("lsa", "bma", "top2", "merge")


@pytest.mark.parametrize("n", [32, 128])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_vmapped(kernel, n, one_chip, chip_compile):
    """The engine's form: unbatched operands per state, vmapped over the
    expanded states and over the pairs of a batch."""
    wrapper, _, spec = _kernel_args(kernel, n, ())
    if kernel == "bma":
        spec = spec[0]
    args = _shapes(one_chip, *(((4, STATES) + s, d) for s, d in spec))
    hlo = chip_compile(jax.vmap(jax.vmap(wrapper)), *args)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_batched(kernel, one_chip, chip_compile):
    """The directly batched form (B = 8 passed straight to the kernel)."""
    _, pallas_fn, spec = _kernel_args(kernel, 128, (8,))
    if kernel == "bma":
        spec = spec[1]
    hlo = chip_compile(lambda *a: pallas_fn(*a, interpret=False),
                       *_shapes(one_chip, *spec))
    assert "tpu_custom_call" in hlo


def test_kernel_search_step_compiles(one_chip, chip_compile):
    """One jitted engine step — the whole vmapped search over a batch of
    64 pairs at 32 slots — with every kernel fused: LSa, BMa, top-2 and
    the merge ranks."""
    from repro.core.engine.api import _run_batch
    from repro.core.engine.search import EngineConfig
    batch, slots = 64, 32
    cfg = EngineConfig(use_kernel=True, dispatch=KernelDispatch(
        lsa_fused=True, bma_fused=True, merge_fused=True))
    args = _shapes(one_chip, ((batch, slots), jnp.int32),
                   ((batch, slots), jnp.int32),
                   ((batch, slots, slots), jnp.int32),
                   ((batch, slots, slots), jnp.int32),
                   ((batch, slots), jnp.int32), ((batch,), jnp.int32),
                   ((batch,), jnp.float32))
    hlo = _run_batch.lower(*args, cfg, True, 62, LE).compile().as_text()
    for name in ("lsa_children", "bma_cost_matrix", "reduced_top2",
                 "merge_rank_counts"):
        assert name in hlo, f"kernel {name} missing from the search step"
    assert "tpu_custom_call" in hlo
