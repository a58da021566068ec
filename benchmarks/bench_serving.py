"""Serving bench: batched predict() throughput while the swarm trains.

Trains the sharded engine in a background thread with
``snapshot_every=1`` (a publication every super-tick) and hammers the
live :class:`repro.serve.ServeHandle` with batched ``predict`` calls
from the foreground — the heavy-traffic read path the paper's
millions-of-users framing implies. Rows:

* ``serving_predictions_per_s`` — rows scored per wall second, measured
  over the concurrent-with-training window;
* ``serving_p50_ms`` / ``serving_p99_ms`` — per-batch predict latency;
* ``serving_publish_us_per_tick`` — snapshot publication cost amortized
  per super-tick (zero-copy tile refs + a slot-counter sync);
* ``serving_version_lag_max`` — worst staleness any request observed,
  in slots (bounded by ``snapshot_every`` while training runs).

The final batch is verified bit-exact against the published snapshot
rows before any row is printed. Run standalone (``JAX_PLATFORMS=cpu``
forces 8 host devices; a chip host uses its chips):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.bench_serving --n 100000

``benchmarks/run.py --only serving`` merges every ``serving_*`` row into
BENCH_summary.json.
"""

from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from repro.launch.runtime import default_shards, force_host_devices


def run(n=100_000, shards=8, slots=4, slot_wakes=2048.0, batch=1024, seed=0,
        verbose=True):
    from repro.core import AgentData, make_objective, random_geometric_graph
    from repro.serve import ServeHandle
    from repro.sim import CDUpdate, EngineConfig, make_engine

    rng = np.random.default_rng(seed)
    p, m = 8, 4
    graph = random_geometric_graph(n, rng, avg_degree=16.0)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    obj = make_objective(
        graph, AgentData(X=X, y=y, mask=np.ones((n, m))), "quadratic",
        mu=0.5, mix_mode="sparse",
    )
    cfg = EngineConfig(slot_wakes=slot_wakes, seed=seed, relabel="rcm")
    eng = make_engine(CDUpdate(obj), cfg, shards=shards)
    handle = ServeHandle.for_engine(eng)

    done = threading.Event()
    box = {}

    def _train():
        try:
            box["result"] = eng.run(np.zeros((n, p)), slots,
                                    snapshot_every=1, serve=handle)
        finally:
            done.set()

    ids = rng.integers(0, n, size=batch)
    Xq = rng.normal(size=(batch, p))

    trainer = threading.Thread(target=_train, name="trainer")
    trainer.start()
    while not done.is_set():
        try:
            handle.version
            break
        except RuntimeError:
            time.sleep(0.002)
    handle.predict(ids, Xq)  # compile outside the timed window

    lat = []
    while not done.is_set():
        t0 = time.perf_counter()
        handle.predict(ids, Xq)
        lat.append(time.perf_counter() - t0)
    trainer.join()
    if "result" not in box:
        raise RuntimeError("training thread died")
    result = box["result"]
    # keep a few post-training samples so tiny configs still measure
    while len(lat) < 16:
        t0 = time.perf_counter()
        handle.predict(ids, Xq)
        lat.append(time.perf_counter() - t0)

    # Served values must be the published snapshot's rows, bit-exact.
    snap = handle.snapshot()
    check = handle.rows(ids[:256], at=snap)
    if snap.version != result.slots or not np.array_equal(
        check.values, result.Theta[ids[:256]].astype(np.float32)
    ):
        raise RuntimeError("served rows diverged from the published snapshot")

    lat = np.asarray(lat)
    c = handle.counters()
    publish_us = 1e6 * c["serve_publish_s_total"] / max(result.slots, 1)
    rows = [
        ("serving_predictions_per_s", batch * lat.size / lat.sum(),
         f"n={n},shards={shards},batch={batch}"),
        ("serving_p50_ms", float(np.percentile(lat, 50) * 1e3),
         f"batch={batch}"),
        ("serving_p99_ms", float(np.percentile(lat, 99) * 1e3),
         f"batch={batch}"),
        ("serving_publish_us_per_tick", publish_us,
         f"snapshots={c['serve_snapshots_published']},slots={result.slots}"),
        ("serving_version_lag_max", float(c["serve_version_lag_max"]),
         "slots behind trainer; bound=snapshot_every=1 while training"),
    ]
    if verbose:
        for name, val, note in rows:
            print(f"{name},{val:.6g},{note}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count (default: 8 on a JAX_PLATFORMS=cpu run, else every device)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--slot-wakes", type=float, default=2048.0)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    shards = args.shards or default_shards(8)
    force_host_devices(shards)
    run(n=args.n, shards=shards, slots=args.slots,
        slot_wakes=args.slot_wakes, batch=args.batch, seed=args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
