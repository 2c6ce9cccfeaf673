"""Device-sharded search_batch throughput: device-count x batch-size sweep.

Measures QPS of the query-data-parallel ``search_batch`` dispatch
(``repro.distributed.query_parallel``) across simulated local device counts
{1, 2, 4, 8} x batch sizes {64, 256} and writes ``BENCH_sharded_search.json``
at the repo root.  XLA fixes the host device count at first init, so every
sweep point runs in a child process launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=<devices>`` (the same
recipe the distributed tests use).  Those children are a CPU rehearsal
only: on a TPU host the sweep stops with an error
(``benchmarks.common.virtual_cpu_env``).

Claims validated:
  * sharding pays even on a small host: 4-device QPS > 1-device QPS at
    batch 256 on the reference path — each device runs its own while_loop,
    so a converged device's 64 lanes stop paying for a straggler device's
    hops (single-device batch-256 pays all 256 lanes until the slowest
    lane converges);
  * sharded results are bit-identical to the single-device path (the
    parent compares result digests across all device counts);
  * recall does not collapse.

``--smoke`` is the CI gate: device counts {1, 2}, tiny N, parity + recall
checks only (QPS ordering on a noisy 2-core CI box is asserted by the full
run, not the gate).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys

DEVICE_COUNTS = (1, 2, 4, 8)
BATCH_SIZES = (64, 256)
M, GAMMA, MBETA = 8, 8, 16
EF, K, D, CARD = 48, 10, 32, 8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(ROOT, "BENCH_sharded_search.json")


def _child(args) -> None:
    """One sweep point per process: fixed device count, all batch sizes."""
    import jax
    import numpy as np

    from repro.core import (ExecutionSpec, VariantCache, build_acorn_gamma,
                            recall_at_k, search_batch)
    from repro.data import make_lcps_dataset, make_workload

    from benchmarks.common import timed_qps

    dp = args.devices
    assert jax.local_device_count() >= dp, (
        f"{jax.local_device_count()} devices; launch via the parent sweep "
        f"so XLA_FLAGS forces {dp}")
    ds = make_lcps_dataset(n=args.n, d=D, card=CARD, seed=0)
    total = max(args.batches)
    wl = make_workload(ds, kind="equals", n_queries=2 * total, k=K, seed=1,
                       card=CARD)
    masks = wl.masks(ds)
    graph = build_acorn_gamma(ds.x, jax.random.PRNGKey(0), M=M, gamma=GAMMA,
                              m_beta=MBETA, compress=False)

    results = []
    digest = None
    for bs in args.batches:
        nq = 2 * bs
        cache = VariantCache()
        kw = dict(k=K, ef=EF, variant="acorn-gamma", m=M, m_beta=MBETA,
                  compressed_level0=False,
                  spec=ExecutionSpec(use_kernel=False,
                                     data_parallel=dp),
                  buckets=(bs,), cache=cache)

        def run_once():
            outs = []
            for s in range(0, nq, bs):
                ids, _, _ = search_batch(graph, ds.x, wl.xq[s:s + bs],
                                         masks[s:s + bs], **kw)
                outs.append(np.asarray(ids))
            return np.concatenate(outs)

        qps = timed_qps(run_once, nq)
        ids = run_once()
        rec = float(recall_at_k(ids, wl.gt(ds)[:nq]))
        if bs == max(args.batches):
            # single-device parity witness: identical across device counts
            digest = hashlib.sha256(ids.tobytes()).hexdigest()
        results.append(dict(devices=dp, batch_size=bs, queries=nq, qps=qps,
                            recall=rec))
    print("BENCH_CHILD_JSON:" + json.dumps(dict(devices=dp, results=results,
                                                ids_digest=digest)))


def _sweep(device_counts, batches, n):
    """Run one child per device count; collect its results + parity digest."""
    from benchmarks.common import virtual_cpu_env
    out = []
    for dp in device_counts:
        env = virtual_cpu_env(dp)
        cmd = [sys.executable, "-m", "benchmarks.bench_sharded_search",
               "--child", "--devices", str(dp),
               "--batches", ",".join(str(b) for b in batches),
               "--n", str(n)]
        r = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                           text=True, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded bench child (devices={dp}) failed:\n"
                f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
        payload = None
        for line in r.stdout.splitlines():
            if line.startswith("BENCH_CHILD_JSON:"):
                payload = json.loads(line[len("BENCH_CHILD_JSON:"):])
        if payload is None:
            raise RuntimeError(f"no child payload (devices={dp}):\n{r.stdout}")
        out.append(payload)
    return out


def run(quick: bool = False, write_json: bool = True):
    device_counts = (1, 2) if quick else DEVICE_COUNTS
    batches = (64,) if quick else BATCH_SIZES
    n = 2048 if quick else 8192
    children = _sweep(device_counts, batches, n)

    results = [r for c in children for r in c["results"]]
    digests = {c["devices"]: c["ids_digest"] for c in children}
    rows = [[f"devices={r['devices']}", r["batch_size"], f"{r['qps']:.1f}",
             f"{r['recall']:.4f}"] for r in results]

    def qps_of(dp, bs):
        return next(r["qps"] for r in results
                    if r["devices"] == dp and r["batch_size"] == bs)

    checks = {
        "sharded_ids_match_single_device":
            len(set(digests.values())) == 1,
        "recall_no_collapse": all(r["recall"] > 0.5 for r in results),
    }
    if not quick:
        checks["dp4_qps_above_dp1_batch256"] = qps_of(4, 256) > qps_of(1, 256)

    if write_json:
        payload = dict(
            config=dict(n=n, d=D, ef=EF, k=K, M=M, gamma=GAMMA, m_beta=MBETA,
                        quick=quick, device_counts=list(device_counts),
                        batch_sizes=list(batches)),
            results=results,
            ids_digests=digests,
            checks={k: bool(v) for k, v in checks.items()},
        )
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    return rows, checks


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI gate; nonzero exit on parity/recall fail")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--batches", type=lambda s: tuple(
        int(b) for b in s.split(",")), default=BATCH_SIZES,
        help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, default=8192, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(args)
        return
    rows, checks = run(quick=args.smoke, write_json=not args.smoke)
    for r in rows:
        print(",".join(str(x) for x in r))
    ok = True
    for name, passed in checks.items():
        print(f"  [{'smoke' if args.smoke else 'claim'}] {name}: "
              f"{'PASS' if passed else 'FAIL'}")
        ok &= bool(passed)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
