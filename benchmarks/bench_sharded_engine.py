"""Sharded async-engine scale bench: agent blocks over host-platform devices.

Where ``bench_async_engine`` drives the single-device batched engine,
this bench shards the agents across S devices via the ``shard_map``
super-tick: per-shard wake batches, a halo exchange of the start-of-slot
border rows, shard-local gather/mix/scatter over shard-resident data
tiles. This is the configuration that takes agent counts past one
device's memory — the bench asserts no O(n^2) array exists anywhere and
reports partition/communication stats alongside super-tick and
equivalent-sequential-tick rates.

Communication sweep: for {no relabel, RCM} x {all_gather, p2p} it
reports the measured halo fraction and the interconnect bytes shipped
per super-tick (rows x p x 4 bytes for the f32 engine dtype) — the
numbers behind the ``exchange="auto"`` selection. The timed run uses
``--relabel``/``--exchange`` (default: RCM + auto).

Run it on a chip host (over every chip) or, with ``JAX_PLATFORMS=cpu``, over
8 forced host devices (``main`` forces them before JAX starts):

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.bench_sharded_engine --n 1000000

``benchmarks/run.py --only sharded_engine`` calls :func:`run` in its own
process and merges every ``sharded_*`` row into the bench summary.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch.runtime import default_shards, force_host_devices


def exchange_stats(graph, shards: int, p: int, partition_mode: str = "degree"):
    """Halo fraction + exchanged bytes/super-tick for the relabel x method grid.

    Pure-numpy partition analysis (no engine build): returns CSV-style
    rows ``(name, value, note)`` for {norelabel, rcm} x {all_gather, p2p},
    plus the built partitions keyed by relabel mode so the caller can
    reuse one for the engine instead of rebuilding it. Bytes assume the
    f32 engine dtype (4 bytes) and count padded rows, because static
    shapes ship them.
    """
    from repro.sim import partition_graph
    from repro.core.mixing import ExchangeSpec, sharded_mix_op

    rows, parts = [], {}
    for label, relabel in (("norelabel", None), ("rcm", "rcm")):
        t0 = time.time()
        part = partition_graph(graph, shards, mode=partition_mode, relabel=relabel)
        build_s = time.time() - t0
        parts[relabel] = part
        auto = sharded_mix_op(part).method
        rows.append(
            (f"sharded_halo_frac_{label}", part.halo_fraction(),
             f"S={shards} mode={partition_mode} auto_method={auto} "
             f"partition_build={build_s:.1f}s")
        )
        for method in ("all_gather", "p2p"):
            xrows = part.exchange_rows(method)
            for dtype in ("f32", "bf16"):
                spec = ExchangeSpec(method=method, dtype=dtype)
                nbytes = xrows * spec.payload_bytes_per_row(p)
                suffix = "" if dtype == "f32" else f"_{dtype}"
                rows.append(
                    (f"sharded_exchange_bytes_{label}_{method}{suffix}",
                     float(nbytes),
                     f"rows={xrows} p={p} {dtype} bytes/super-tick")
                )
    return rows, parts


def run(
    n: int = 1_000_000,
    p: int = 8,
    m: int = 4,
    shards: int = 8,
    slots: int = 8,
    slot_wakes: float = 8192.0,
    seed: int = 0,
    churn: bool = True,
    partition_mode: str = "degree",
    relabel: str | None = "rcm",
    exchange: str = "auto",
    metrics: bool = False,
    roofline: bool | None = None,
    verbose: bool = True,
):
    """Time the sharded engine at scale and report the comm sweep rows.

    ``exchange`` takes an :class:`repro.core.mixing.ExchangeSpec` or a
    spec string (``"auto"``, ``"p2p:bf16"``, ``"p2p:int8:ef"`` ...).
    ``roofline`` places the super-tick against the chip's peaks
    (:mod:`repro.roofline.peaks`); None does so on a TPU only, since the
    CPU has no peaks to place it against.
    """
    import jax

    if roofline is None:
        roofline = jax.default_backend() == "tpu"

    from benchmarks.bench_sparse_scale import _make_problem
    from repro.core.mixing import ExchangeSpec
    from repro.sim import CDUpdate, ChurnConfig, Scenario, ShardedAsyncEngine

    if len(jax.devices()) < shards:
        raise RuntimeError(
            f"need {shards} devices (have {len(jax.devices())}); on the CPU "
            "run main() under JAX_PLATFORMS=cpu, which forces them"
        )

    rng = np.random.default_rng(seed)
    t0 = time.time()
    graph, obj = _make_problem(n, p, m, rng)
    build_s = time.time() - t0

    # Communication sweep: {no relabel, RCM} x {all_gather, p2p}. The
    # sweep's partitions are reused for the timed engine when the config
    # matches, so the (RCM + cut + tile) build runs once, not twice.
    stats_rows, parts = exchange_stats(graph, shards, p, partition_mode)

    scenario = Scenario(
        churn=ChurnConfig(leave_prob=0.01, rejoin_prob=0.2) if churn else None
    )
    spec = exchange if isinstance(exchange, ExchangeSpec) else ExchangeSpec.from_string(exchange)
    t0 = time.time()
    engine = ShardedAsyncEngine(
        CDUpdate(obj),
        num_shards=shards,
        partition_mode=partition_mode,
        relabel=relabel,
        exchange=spec,
        partition=parts.get(relabel),
        slot_wakes=slot_wakes,
        scenario=scenario,
        seed=seed,
        metrics=metrics,
    )
    part_s = time.time() - t0
    part = engine.part

    # No (n, n) array anywhere: the shard tiles are O(nnz)-with-padding and
    # the halo/border maps O(cut); same guard floor as the sparse bench.
    mix = obj.mix
    leak_floor = max(n * n // 100, 64 * n + 256)
    for arr in (
        mix.idx, mix.w, mix.rows, mix.cols, mix.vals,
        part.idx, part.w, part.border, part.halo_src, part.owned,
    ):
        assert arr is None or arr.size < leak_floor, "an O(n^2) array leaked in"

    state = engine.init_state(np.zeros((n, p)))
    t0 = time.time()
    state = engine.advance(state, slots)
    state.Theta.block_until_ready()
    compile_s = time.time() - t0
    warm_applied = int(np.asarray(state.applied).sum())

    t0 = time.time()
    state = engine.advance(state, slots)
    state.Theta.block_until_ready()
    steady_s = time.time() - t0

    Theta = engine.global_theta(state)
    assert np.isfinite(Theta).all()
    applied = int(np.asarray(state.applied).sum())
    steady_applied = applied - warm_applied
    assert steady_applied > 0
    ticks_per_s = steady_applied / max(steady_s, 1e-9)
    deg = np.diff(graph.indptr)
    wire = engine.exchange_spec
    xbytes = part.exchange_rows(engine.exchange_method) * wire.payload_bytes_per_row(p)
    rows = [
        ("sharded_graph_build", build_s * 1e6 / max(n, 1),
         f"n={n} deg~{deg.mean():.1f} us/agent"),
        ("sharded_engine_build", part_s * 1e6 / max(n, 1),
         f"S={shards} mode={partition_mode} relabel={relabel} R={part.rows_per_shard} "
         f"halo_frac={part.halo_fraction():.3f} us/agent "
         "(partition reused from the sweep; per-config partition_build "
         "times are on the halo_frac rows)"),
        ("sharded_super_tick", steady_s * 1e6 / slots,
         f"n={n} S={shards} B={engine.batch_size} churn={int(churn)} "
         f"exchange={engine.exchange_method}:{wire.dtype}"
         f"{':ef' if wire.error_feedback else ''} "
         f"xbytes={xbytes} us/slot"),
        ("sharded_equiv_ticks_per_s", ticks_per_s,
         f"{applied} wakes applied, {int(np.asarray(state.dropped).sum())} dropped, "
         f"compile {compile_s:.1f}s"),
    ] + stats_rows
    if metrics:
        # In-jit telemetry totals (counters were live through the timed
        # halves, so the super-tick row above already includes their cost).
        from repro.obs import summarize_counters

        counters, _derived = engine.metrics_snapshot(state)
        totals = summarize_counters(counters)
        for key in ("wakes_realized", "exchange_bytes", "churn_departures"):
            if key in totals:
                rows.append(
                    (f"sharded_metrics_{key}", float(totals[key]),
                     f"telemetry total over {2 * slots} slots, summed over shards")
                )
    if roofline:
        # Place the compiled super-tick on the bandwidth roofline (the
        # program advance() just ran, compressed halos included) and report the measured-vs-bound gap.
        from repro.roofline import supertick_report

        rows += supertick_report(
            engine, state=state, steps=slots,
            measured_s_per_tick=steady_s / slots,
            prefix="sharded_roofline_supertick",
        )
    if verbose:
        for name, v, note in rows:
            print(f"{name},{v:.4g},{note}")
    return rows


def main(argv=None):
    """CLI entry point; forces host-platform devices when still possible."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count (default: 8 on a JAX_PLATFORMS=cpu run, else every device)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--slot-wakes", type=float, default=8192.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-churn", action="store_true")
    ap.add_argument("--mode", default="degree", choices=["degree", "contiguous"])
    ap.add_argument("--relabel", default="rcm", choices=["rcm", "none"])
    ap.add_argument("--exchange", default="auto",
                    help="ExchangeSpec string: method[:dtype[:ef]] with method "
                         "auto|all_gather|p2p and dtype f32|bf16|int8 "
                         "(e.g. p2p:bf16, p2p:int8:ef)")
    ap.add_argument("--metrics", action="store_true",
                    help="run with in-jit telemetry on and report its totals")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args(argv)
    shards = args.shards or default_shards(8)
    force_host_devices(shards)
    run(
        n=args.n,
        shards=shards,
        slots=args.slots,
        slot_wakes=args.slot_wakes,
        seed=args.seed,
        churn=not args.no_churn,
        partition_mode=args.mode,
        relabel=None if args.relabel == "none" else args.relabel,
        exchange=args.exchange,
        metrics=args.metrics,
        roofline=False if args.no_roofline else None,
    )


if __name__ == "__main__":
    main()
