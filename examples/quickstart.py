"""Quickstart: one front door for GED — ``repro.ged``.

    PYTHONPATH=src python examples/quickstart.py   # or pip install -e .

Every entry point — module-level one-shots, a configured ``GedEngine``,
or streaming ``submit``/``flush`` — returns the same ``GedOutcome``
schema, whichever backend answered.
"""

import numpy as np

from repro import ged
from repro.core.exact.graph import Graph
from repro.ged.exec import enable_compile_cache

# jax's persistent compile cache: JAX_COMPILATION_CACHE_DIR when set, else
# .jax_cache/ in the checkout — a second run skips the engine compiles
enable_compile_cache()

# --- build the paper's Figure 3 pair ---------------------------------------
A, B, C = 0, 1, 2
a, b = 1, 2
q = Graph.from_edges([A, B, B, B],
                     [(0, 1, a), (1, 2, a), (2, 3, b), (1, 3, b)])
g = Graph.from_edges([B, B, B, B, C],
                     [(0, 1, a), (1, 2, b), (2, 3, b), (1, 3, b),
                      (0, 4, b), (3, 4, a)])

# --- one-shot, paper-faithful host solver (AStar+-BMa, Alg. 2 + §4) --------
[ref] = ged.compute([(q, g)], backend="exact")
print(f"exact backend : delta(q, g) = {ref.ged}  "
      f"(certified={ref.certified}, mapping={ref.mapping})")

[ver] = ged.verify([(q, g)], tau=5.0, backend="exact")
print(f"verification  : delta(q, g) <= 5 ? {ver.similar}")

# --- graphs don't have to be Graph objects ---------------------------------
# (vlabels, edges) tuples and adjacency dicts are ingested automatically
q_edges = ([A, B, B, B], [(0, 1, a), (1, 2, a), (2, 3, b), (1, 3, b)])
[same] = ged.compute([(q_edges, g)], backend="exact")
assert same.ged == ref.ged

# --- batched JAX engine: same answers, thousands of pairs at once ----------
rng = np.random.default_rng(0)
from repro.data.graphs import perturb, random_graph
pairs = [(q, g)]
for _ in range(15):
    qq = random_graph(rng, 10)
    pairs.append((qq, perturb(rng, qq, 3)))

engine = ged.GedEngine(backend="jax", pool=512, expand=8)
outs = engine.compute(pairs)
print(f"\njax backend   : {len(pairs)} pairs, bucketed into power-of-two "
      f"shapes ({engine.stats})")
print("  ged      :", [int(o.ged) for o in outs[:8]], "...")
print("  certified:", [o.certified for o in outs[:8]], "...")

vers = engine.verify(pairs, tau=4.0)
print("  <= 4?    :", [o.similar for o in vers[:8]], "...")

# --- mesh-sharded execution: same policy, shard_map placement --------------
# The sharded backend shards the pair batch over every local device
# (or a mesh you pass via ``mesh=``); batches are padded to shard
# multiples automatically, and outcomes are identical to the jax backend.
import jax
sharded = ged.GedEngine(backend="sharded", pool=512, expand=8)
outs_sh = sharded.compute(pairs)
assert [o.ged for o in outs_sh] == [o.ged for o in outs]
print(f"\nsharded       : {len(pairs)} pairs over {jax.device_count()} "
      f"device(s), batch multiple {sharded.batch_multiple}")

# --- engine-level result cache: duplicates never re-execute ----------------
again = sharded.compute(pairs)              # same pairs -> pure cache hits
assert all(o.stats.get("cached") for o in again)
print(f"result cache  : {sharded.stats['result_cache_hits']} hits, "
      f"{sharded.stats['result_cache_misses']} misses")

# --- streaming: mix computation and verification, flush once ---------------
engine.submit(q, g)                  # computation ticket 0
engine.submit(q, g, tau=4.0)         # verification ticket 1
t0, t1 = engine.flush()
print(f"\nstreaming     : ged={t0.ged}, <=4? {t1.similar}")

# --- the escalating production pipeline (always certified) -----------------
auto = ged.GedEngine(backend="auto", batch_size=8)
assert all(o.certified for o in auto.compute(pairs))

assert int(outs[0].ged) == ref.ged
print("\nall backends agree through one facade ✓")
