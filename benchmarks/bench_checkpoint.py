"""Checkpoint bench: save/restore wall time and on-disk bytes, per-shard.

Times one :func:`repro.checkpoint.save_engine_checkpoint` +
:func:`repro.checkpoint.restore` round trip of the sharded engine's full
resume closure (Theta tiles, churn mask, update state, counters,
metrics) at benchmark scale, without ever materializing the (n, p) model
matrix on the host — the per-shard layout is exactly what makes the cost
O(n/S) resident memory per shard file. Rows:

* ``ckpt_save_s`` — state_dict + staged fsync'd write + atomic rename;
* ``ckpt_restore_s`` — verify hashes, re-tile shard files, rebuild state;
* ``ckpt_bytes`` — total entry size on disk;
* ``ckpt_mb_per_s`` — save throughput (bytes / save seconds).

Run standalone (``JAX_PLATFORMS=cpu`` forces 8 host devices; a chip host
uses its chips):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.bench_checkpoint --n 200000

``benchmarks/run.py --only checkpoint`` merges every ``ckpt_*`` row into
BENCH_summary.json.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time

import numpy as np

from repro.launch.runtime import default_shards, force_host_devices


def run(n=200_000, shards=8, slots=2, slot_wakes=2048.0, seed=0, verbose=True):
    import jax.numpy as jnp

    from repro.checkpoint import restore, save_engine_checkpoint
    from repro.core import AgentData, make_objective, random_geometric_graph
    from repro.sim import CDUpdate, ShardedAsyncEngine

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
    eng = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=shards, slot_wakes=slot_wakes, seed=seed,
        relabel="rcm", metrics=True, dtype=jnp.float32,
    )
    res = eng.run(np.zeros((n, p)), slots=slots)
    state = res.state

    rows = []
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        t0 = time.time()
        entry = save_engine_checkpoint(eng, state, ck)
        save_s = time.time() - t0
        nbytes = sum(
            os.path.getsize(os.path.join(entry, f)) for f in os.listdir(entry)
        )
        fresh = ShardedAsyncEngine(
            CDUpdate(obj), num_shards=shards, slot_wakes=slot_wakes, seed=seed,
            relabel="rcm", metrics=True, dtype=jnp.float32,
        )
        t0 = time.time()
        restored, step = restore(fresh, ck)
        restore_s = time.time() - t0
        assert step == slots
        np.testing.assert_array_equal(
            np.asarray(restored.Theta), np.asarray(state.Theta)
        )
    note = f"n={n},shards={shards}"
    rows.append(("ckpt_save_s", save_s, note))
    rows.append(("ckpt_restore_s", restore_s, note))
    rows.append(("ckpt_bytes", float(nbytes), note))
    rows.append(("ckpt_mb_per_s", nbytes / save_s / 1e6, f"{note},save throughput"))
    if verbose:
        for name, v, note in rows:
            print(f"{name},{v:.4g},{note}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count (default: 8 on a JAX_PLATFORMS=cpu run, else every device)")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--slot-wakes", type=float, default=2048.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    shards = args.shards or default_shards(8)
    force_host_devices(shards)
    run(n=args.n, shards=shards, slots=args.slots,
        slot_wakes=args.slot_wakes, seed=args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
