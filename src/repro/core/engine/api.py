"""Batched engine entry points.

.. deprecated::
    ``ged_batch`` / ``verify_batch`` are kept as thin shims for existing
    callers; new code should go through the facade in :mod:`repro.ged`
    (``repro.ged.GedEngine`` / ``repro.ged.compute``), which adds input
    adapters, slot bucketing with compile-cache reuse, backend selection and
    the unified ``GedOutcome`` result schema.

Pairs are data-parallel: ``vmap`` on one device; ``shard_map`` over the mesh
(``pod`` x ``data`` x ``model`` all carry pairs) at scale — the placement
layer lives in :mod:`repro.ged.exec` (``Executor`` / ``ShardedExecutor``);
see also ``repro/serving/ged_service.py`` and ``launch/dryrun.py``.
"""

from __future__ import annotations

import functools
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.search import EngineConfig, run_pair
from repro.core.engine.tensor_graphs import GraphPairTensors, pack_pairs

# Number of times ``_run_batch`` has been *traced* (compiled) this process.
# The increment below runs only while JAX traces the function, so bucketed
# workloads that reuse a compilation do not bump it — ``repro.ged.plan``'s
# bucketing tests assert on this.
_RUN_BATCH_TRACES = 0


def run_batch_traces() -> int:
    """How many distinct compilations of the batch kernel exist."""
    return _RUN_BATCH_TRACES


def pair_tuple(t: GraphPairTensors):
    """Device-array argument tuple for ``_run_batch``."""
    return (jnp.asarray(t.qv), jnp.asarray(t.gv), jnp.asarray(t.qa),
            jnp.asarray(t.ga), jnp.asarray(t.order), jnp.asarray(t.n))


_pair_tuple = pair_tuple  # backwards-compatible private alias


@functools.partial(jax.jit, static_argnames=("cfg", "verification",
                                             "n_vlabels", "n_elabels"))
def _run_batch(qv, gv, qa, ga, order, n, taus, cfg: EngineConfig,
               verification: bool, n_vlabels: int, n_elabels: int):
    global _RUN_BATCH_TRACES
    _RUN_BATCH_TRACES += 1  # trace-time side effect: counts compilations

    def one(qv, gv, qa, ga, order, n, tau):
        return run_pair((qv, gv, qa, ga, order, n, n_vlabels, n_elabels),
                        cfg, tau, verification)

    return jax.vmap(one)(qv, gv, qa, ga, order, n, taus)


def ged_batch(pairs: GraphPairTensors, cfg: EngineConfig = EngineConfig()
              ) -> Dict[str, np.ndarray]:
    """Exact-with-certificate GED for a batch of pairs.

    .. deprecated:: use ``repro.ged.GedEngine(backend="jax").compute``.
    """
    warnings.warn(
        "ged_batch is deprecated and will be removed in repro-ged 0.3; "
        "use repro.ged.GedEngine / repro.ged.compute (corpus workloads: "
        "repro.ged.GraphStore)",
        DeprecationWarning, stacklevel=2)
    args = pair_tuple(pairs)
    taus = jnp.zeros((pairs.batch,), dtype=jnp.float32)
    out = _run_batch(*args, taus, cfg, False, pairs.n_vlabels, pairs.n_elabels)
    out = {k: np.asarray(v) for k, v in out.items()}
    out["ged"] = np.where(out["exact"], np.rint(out["ged"]), out["ged"])
    return out


def verify_batch(pairs: GraphPairTensors, taus: Sequence[float],
                 cfg: EngineConfig = EngineConfig()) -> Dict[str, np.ndarray]:
    """Batched GED verification: ``delta(q, g) <= tau``? per pair.

    .. deprecated:: use ``repro.ged.GedEngine(backend="jax").verify``.
    """
    warnings.warn(
        "verify_batch is deprecated and will be removed in repro-ged 0.3; "
        "use repro.ged.GedEngine / repro.ged.verify (corpus workloads: "
        "repro.ged.GraphStore.range_search)",
        DeprecationWarning, stacklevel=2)
    args = pair_tuple(pairs)
    taus = jnp.asarray(np.asarray(taus, dtype=np.float32))
    out = _run_batch(*args, taus, cfg, True, pairs.n_vlabels, pairs.n_elabels)
    return {k: np.asarray(v) for k, v in out.items()}


def batch_abstract_inputs(batch: int, slots: int):
    """ShapeDtypeStruct stand-ins for a verification batch (for dry-runs)."""
    f = jax.ShapeDtypeStruct
    return dict(
        qv=f((batch, slots), jnp.int32),
        gv=f((batch, slots), jnp.int32),
        qa=f((batch, slots, slots), jnp.int32),
        ga=f((batch, slots, slots), jnp.int32),
        order=f((batch, slots), jnp.int32),
        n=f((batch,), jnp.int32),
        taus=f((batch,), jnp.float32),
    )
