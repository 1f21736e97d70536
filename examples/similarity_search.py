"""Graph similarity search over a database — the paper's target application
(§1, §5.3), end to end through ``repro.ged.GraphStore``.

A molecule corpus is ingested once (shared label vocab, resident stage-0
feature arrays, WL-digest dedup, a banded WL-sketch candidate index);
queries then run the staged filter-verify pipeline: the sound sketch
index prunes most of the corpus without scanning it (``docs/index.md``),
a vectorized corpus scan prunes the survivors with label/degree/size
bounds, the anchor-aware engine bounds decide most of the rest at a tiny
budget, and only the remainder pays full certified verification
(``docs/search.md``).

    PYTHONPATH=src python examples/similarity_search.py
"""

import time

import numpy as np

from repro.data.graphs import aids_like_graph, perturb
from repro.ged import GraphStore
from repro.ged.exec import enable_compile_cache

# jax's persistent compile cache: JAX_COMPILATION_CACHE_DIR when set, else
# .jax_cache/ in the checkout — a second run skips the engine compiles
enable_compile_cache()

rng = np.random.default_rng(1)

# --- database: 80 AIDS-like molecules, some of them near-copies of others --
DB = []
for i in range(60):
    DB.append(aids_like_graph(rng, int(rng.integers(8, 14))))
query = DB[0]
for _ in range(20):                       # planted near-duplicates
    DB.append(perturb(rng, query, int(rng.integers(1, 5)),
                      n_vlabels=62, n_elabels=3))

TAU = 4.0
store = GraphStore(DB, batch_size=32, slots=16)

t0 = time.time()
hits = store.range_search(query, TAU)
dt = time.time() - t0

stats = store.stats
print(f"database size  : {len(DB)}")
print(f"tau            : {TAU}")
print(f"similar graphs : {len(hits)} -> ids "
      f"{[h.graph_id for h in hits[:12]]}{'...' if len(hits) > 12 else ''}")
print(f"wall time      : {dt:.2f}s "
      f"(scan {stats['scan_wall_s'] + stats['bound_wall_s']:.2f}s, "
      f"verify {stats['verify_wall_s']:.2f}s)")
print(f"all certified  : {all(h.certified for h in hits)}")
print(f"filter ratio   : {stats['filter_ratio']:.2%} of "
      f"{int(stats['candidates'])} candidates decided before verification "
      f"(index pruned {int(stats['index_pruned'])}, "
      f"stage 0 pruned {int(stats['stage0_pruned'])})")

# the same ingested corpus answers nearest-neighbour queries: visit
# candidates in lower-bound order, stop once the bound passes the k-th best
top = store.top_k(query, k=5)
print(f"top-5 by GED   : {[(h.graph_id, h.ged) for h in top]}")

# sanity: the planted near-duplicates with few edits should be among hits
planted = set(range(60, 80))
found_planted = planted & {h.graph_id for h in hits}
print(f"planted near-duplicates found: {len(found_planted)}/20")
assert any(h.graph_id == 0 for h in hits), "query vs itself must be similar"
assert top[0].graph_id == 0 and top[0].ged == 0.0
