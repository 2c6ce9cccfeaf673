"""Quickstart: build an ACORN index over a multi-modal synthetic corpus and
run hybrid queries (vector similarity + structured predicates) through the
query-plan API: SearchRequest in, compiled predicate program underneath.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core import (AcornConfig, Between, ContainsAny, ExecutionSpec,
                        HybridIndex, SearchRequest, recall_at_k)
from repro.data import make_hcps_dataset, make_workload

# 1. a corpus: vectors + keyword lists + dates + captions
ds = make_hcps_dataset(n=6000, d=32, seed=0)
print(f"corpus: {ds.n} vectors x {ds.d} dims, "
      f"columns: {list(ds.table.int_cols) + list(ds.table.bitset_cols)}")

# 2. build ACORN-gamma (predicate-agnostic: no predicate knowledge needed)
cfg = AcornConfig(M=16, gamma=12, m_beta=32, ef_search=96)
index = HybridIndex.build(ds.x, ds.table, cfg, seed=0)
print(f"ACORN-gamma built in {index.build_seconds:.1f}s | "
      f"index {index.index_bytes / 1e6:.1f} MB "
      f"(+{ds.x.size * 4 / 1e6:.1f} MB vectors)")

# 3. hybrid queries: nearest images that contain a keyword AND a date range.
#    A SearchRequest bundles queries + predicates + k; the predicate trees
#    compile into ONE fused on-device program (no per-predicate dispatch).
wl = make_workload(ds, kind="contains+between", n_queries=16, k=10, seed=1)
request = SearchRequest(xq=wl.xq, predicates=wl.predicates, k=10)
ids, dists, info = index.search(request)
print(f"recall@10 = {recall_at_k(ids, wl.gt(ds)):.3f} | routes: "
      f"{dict(zip(*np.unique(info['routes'], return_counts=True)))}")

# 3b. execution policy is one value — e.g. flip the Pallas kernels on
#     (compiled on a TPU; the CPU can only interpret them):
on_cpu = jax.devices()[0].platform == "cpu"
ids_k, _, _ = index.search(request, spec=ExecutionSpec(use_kernel=True,
                                                       interpret=on_cpu))
print("kernel path identical ids:",
      bool((np.asarray(ids) == np.asarray(ids_k)).all()))

# 4. ad-hoc predicate composition — the set is unbounded by design; a
#    pre-compiled program can be reused across calls (index.compile)
q = ds.x[123:124]
pred = ContainsAny("keywords", (2, 7)) & Between("date", 30, 60)
program = index.compile([pred])
ids, dists, _ = index.search(SearchRequest(xq=q, predicates=program, k=5))
print("ad-hoc query top-5 ids:", ids[0].tolist())
