"""Benchmark entry point — one bench per paper table/figure + scale/roofline.

    PYTHONPATH=src python -m benchmarks.run            # fast mode (CI-sized)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale
    PYTHONPATH=src python -m benchmarks.run --list     # show bench names
    PYTHONPATH=src python -m benchmarks.run --only obs # run one bench

All benches run in this one process, so a chip run holds its chip once:
on a chip host the multi-device benches shard over the host's chips; with
``JAX_PLATFORMS=cpu`` they shard over 8 forced host devices.

Prints ``name,us_per_call,derived`` CSV lines per bench plus per-table
summaries. Every run (fast mode included) writes the machine-readable
``results/BENCH_summary.json`` mapping name -> {us_per_call, derived} so
the perf trajectory accumulates per PR; paper-scale results additionally
land in results/*.json and EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time


# Host devices a JAX_PLATFORMS=cpu run forces for the multi-device benches.
HOST_DEVICES = 8


def _ensure_src():
    """Make ``repro`` importable when it is not installed."""
    try:
        import repro  # noqa: F401
    except ImportError:
        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
        )


def _merge_summary(path, rows):
    """Shared with the report CLI so the two summary writers cannot drift."""
    from repro.obs.report import merge_bench_summary

    merge_bench_summary(path, rows)


def _shard_bench(module: str, row_prefix: str, **kw) -> tuple[int, list]:
    """Run a multi-device bench module's ``run`` in this process.

    It runs on every device the process has: the chip host's, or on a
    ``JAX_PLATFORMS=cpu`` run the 8 host devices ``main`` forced before
    JAX started. One process holds the chip, so no child is started.
    Returns the shard count and every ``row_prefix*`` row it returned.
    """
    import importlib

    from repro.launch.runtime import default_shards

    shards = default_shards(HOST_DEVICES)
    rows = importlib.import_module(module).run(shards=shards, **kw)
    rows = [r for r in rows if r[0].startswith(row_prefix)]
    if not rows:
        raise RuntimeError(f"{module} returned no {row_prefix}* rows")
    return shards, rows


def _bench_fig1(full, rows, record):
    from benchmarks import bench_cd_vs_admm

    t0 = time.time()
    kw = {} if full else dict(n=30, p=20, T_cd=800, T_admm=80)
    r = bench_cd_vs_admm.run(out="results/fig1_cd_vs_admm.json", **kw)
    record("fig1_cd_vs_admm", t0,
           f"cd_beats_admm_per_message={r['cd_beats_admm_per_message']}")


def _bench_fig2(full, rows, record):
    from benchmarks import bench_privacy_utility

    t0 = time.time()
    r = bench_privacy_utility.run(out="results/fig2_privacy_utility.json",
                                  fast=not full)
    acc = r["fig2c"][-1]
    record("fig2_privacy_utility", t0,
           f"acc_local={acc['acc_local']:.3f},acc_nonpriv={acc['acc_nonprivate']:.3f}")


def _bench_table1(full, rows, record):
    from benchmarks import bench_movielens

    t0 = time.time()
    r = bench_movielens.run(out="results/table1_movielens_fastmode.json",
                            fast=not full)
    record("table1_movielens", t0,
           f"rmse_local={r['rmse_local']:.3f},rmse_cd={r['rmse_cd']:.3f}")


def _bench_ablations(full, rows, record):
    from benchmarks import bench_ablations

    t0 = time.time()
    r = bench_ablations.run(out="results/ablations.json", fast=not full)
    record("ablations", t0,
           f"personalized={r['personalization']['acc_personalized']:.3f},"
           f"global={r['personalization']['acc_global']:.3f}")


def _bench_kernels(full, rows, record):
    from benchmarks import bench_kernels

    t0 = time.time()
    ks = bench_kernels.run()
    # Per-kernel rows (fused_row_update etc.) join the summary alongside
    # the aggregate, so kernel-level perf has its own trajectory.
    rows.extend(ks)
    record("kernels", t0, f"{len(ks)} kernels timed")


def _bench_sparse_scale(full, rows, record):
    from benchmarks import bench_sparse_scale

    t0 = time.time()
    kw = dict(n=100_000, ticks=2_000) if full else dict(n=5_000, ticks=200)
    ss = bench_sparse_scale.run(verbose=False, **kw)
    tick_us = next(v for name, v, _ in ss if name == "sparse_cd_tick")
    record("sparse_scale", t0, f"n={kw['n']},us_per_seq_tick={tick_us:.3g}")


def _bench_async_engine(full, rows, record):
    from benchmarks import bench_async_engine

    t0 = time.time()
    kw = (
        dict(n=500_000, slots=12, slot_wakes=4096.0)
        if full
        else dict(n=20_000, slots=4, slot_wakes=512.0)
    )
    ae = bench_async_engine.run(churn=True, verbose=False, **kw)
    rate = next(v for name, v, _ in ae if name == "async_equiv_ticks_per_s")
    record("async_engine", t0, f"n={kw['n']},churn=1,equiv_ticks_per_s={rate:.4g}")


def _bench_sharded_engine(full, rows, record):
    t0 = time.time()
    kw = (
        dict(n=1_000_000, slots=8, slot_wakes=8192.0)
        if full
        else dict(n=100_000, slots=4, slot_wakes=2048.0)
    )
    # Tick rates, partition stats, the halo-fraction / exchanged-bytes
    # sweep over {no relabel, RCM} x {all_gather, p2p} — every sharded_*
    # row the bench returns joins the summary under its own name.
    shards, sub = _shard_bench("benchmarks.bench_sharded_engine", "sharded_", **kw)
    rows.extend(sub)
    rate = next(
        (v for name, v, _ in sub if name == "sharded_equiv_ticks_per_s"), None
    )
    if rate is None:
        raise RuntimeError("sharded_engine printed no sharded_equiv_ticks_per_s row")
    record("sharded_engine", t0,
           f"n={kw['n']},shards={shards},equiv_ticks_per_s={rate:.4g}")


def _bench_obs(full, rows, record):
    t0 = time.time()
    # Keep the slot loaded (>=2048 wakes) even in fast mode: the overhead
    # comparison divides a ~100us-scale metrics delta by the slot time, so
    # an under-loaded slot reads as inflated percentage (pure noise).
    kw = (
        dict(n=200_000, slots=8, slot_wakes=4096.0)
        if full
        else dict(n=50_000, slots=6, slot_wakes=2048.0)
    )
    # Telemetry overhead (metrics-on vs off, target <=5%) and the
    # obs_phase_* decomposition of the super-tick behind the
    # sharded_roofline_supertick_gap row; also writes the trace.json and
    # RunReport JSONL artifacts under results/.
    shards, sub = _shard_bench("benchmarks.bench_obs", "obs_", **kw)
    rows.extend(sub)
    over = next((v for name, v, _ in sub if name == "obs_overhead"), None)
    if over is None:
        raise RuntimeError("obs bench printed no obs_overhead row")
    record("obs", t0, f"n={kw['n']},shards={shards},overhead_pct={over:.3g}")


def _bench_dynamic_topology(full, rows, record):
    from benchmarks import bench_dynamic_topology

    t0 = time.time()
    kw = dict(n=200_000, shards=8) if full else dict(n=20_000, shards=8)
    dt = bench_dynamic_topology.run(verbose=False, **kw)
    # Host-side partition machinery: patch-vs-rebuild timings, the drift
    # gauge, and the (asserted) halo parity row all join the summary.
    rows.extend(dt)
    speedup = next(v for name, v, _ in dt if name == "dyntopo_patch_speedup")
    record("dynamic_topology", t0, f"n={kw['n']},patch_speedup={speedup:.3g}")


def _bench_checkpoint(full, rows, record):
    t0 = time.time()
    kw = dict(n=200_000) if full else dict(n=20_000)
    # Engine save/restore round trip at scale: wall seconds each way plus
    # entry bytes, all per-shard with no (n, p) host materialization.
    shards, sub = _shard_bench("benchmarks.bench_checkpoint", "ckpt_", **kw)
    rows.extend(sub)
    save_s = next((v for name, v, _ in sub if name == "ckpt_save_s"), None)
    nbytes = next((v for name, v, _ in sub if name == "ckpt_bytes"), None)
    if save_s is None or nbytes is None:
        raise RuntimeError("checkpoint bench printed no ckpt_save_s/ckpt_bytes rows")
    record("checkpoint", t0,
           f"n={kw['n']},shards={shards},save_s={save_s:.3g},bytes={int(nbytes)}")


def _bench_serving(full, rows, record):
    t0 = time.time()
    kw = (
        dict(n=1_000_000, slots=6, slot_wakes=8192.0, batch=1024)
        if full
        else dict(n=100_000, slots=4, slot_wakes=2048.0, batch=512)
    )
    # Live read path: batched predict() against the newest published
    # snapshot while the sharded engine trains — predictions/s, p50/p99
    # batch latency, and the per-super-tick publication cost all join
    # the summary (served rows are asserted bit-exact in-bench).
    shards, sub = _shard_bench("benchmarks.bench_serving", "serving_", **kw)
    rows.extend(sub)
    rate = next(
        (v for name, v, _ in sub if name == "serving_predictions_per_s"), None
    )
    if rate is None:
        raise RuntimeError("serving bench printed no serving_predictions_per_s row")
    record("serving", t0,
           f"n={kw['n']},shards={shards},batch={kw['batch']},"
           f"predictions_per_s={rate:.4g}")


def _bench_roofline(full, rows, record):
    from benchmarks import bench_roofline

    t0 = time.time()
    rs = bench_roofline.run()
    if not rs:
        # No dry-run output on this backend/config: say so and record
        # nothing, instead of emitting an empty "0 dry-run rows" row
        # into BENCH_summary.json that reads like a measurement.
        print("roofline: skipped (no dry-run rows on this backend)")
        return
    record("roofline", t0, f"{len(rs)} dry-run rows")


# The benches whose paper-level results are computed in f64.
X64_BENCHES = frozenset({"fig1", "fig2", "table1", "ablations"})

# Registration order is execution order; roofline stays last so its
# dry-run rows print after the measured ones they contextualize.
BENCHES = {
    "fig1": _bench_fig1,
    "fig2": _bench_fig2,
    "table1": _bench_table1,
    "ablations": _bench_ablations,
    "kernels": _bench_kernels,
    "sparse_scale": _bench_sparse_scale,
    "async_engine": _bench_async_engine,
    "sharded_engine": _bench_sharded_engine,
    "obs": _bench_obs,
    "dynamic_topology": _bench_dynamic_topology,
    "checkpoint": _bench_checkpoint,
    "serving": _bench_serving,
    "roofline": _bench_roofline,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale (slow)")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single bench (see --list)")
    ap.add_argument("--list", action="store_true", help="list bench names and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in BENCHES:
            print(name)
        return 0
    if args.only is not None and args.only not in BENCHES:
        print(
            f"unknown bench {args.only!r}; valid names: {', '.join(BENCHES)}",
            file=sys.stderr,
        )
        return 2

    _ensure_src()
    from repro.launch.runtime import enable_compile_cache, force_host_devices

    # Before JAX starts: a CPU run splits the host into the devices the
    # multi-device benches shard over; a chip run keeps its chips.
    force_host_devices(HOST_DEVICES)
    import jax

    enable_compile_cache()
    os.makedirs("results", exist_ok=True)
    rows = []

    def record(name, t0, derived):
        us = (time.time() - t0) * 1e6
        rows.append((name, us, derived))
        print(f"{name},{us:.0f},{derived}")

    for name, bench in BENCHES.items():
        if args.only in (None, name):
            # The paper-core benches compute in f64; everything else runs
            # in the 32-bit mode the engine runs in on the chip.
            with jax.enable_x64(name in X64_BENCHES):
                bench(args.full, rows, record)

    # Machine-readable per-PR perf trajectory (fast mode and --only runs
    # included): the stable contract is name -> {us_per_call, derived},
    # merged into the existing map so a partial --only run updates its own
    # entries without clobbering the accumulated trajectory. Written once
    # under results/ and copied byte-identical to the repo root, where the
    # perf-history tooling looks (tools/check_bench_sync.py asserts the
    # two stay in sync).
    _merge_summary("results/BENCH_summary.json", rows)
    shutil.copyfile("results/BENCH_summary.json", "BENCH_summary.json")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
