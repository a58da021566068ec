"""Observability bench: what does telemetry cost?

The same sharded run (the 8-shard engine the roofline rows describe) is
timed metrics-off and metrics-on (full :class:`repro.obs.MetricsSpec`);
the ``obs_overhead`` row reports the steady-state super-tick overhead in
percent. The acceptance target is <= 5% — the counters only re-reduce
values the slot already computed, so most of the "overhead" is timing
noise. Where the super-tick's time goes is read from a device trace by
the benchmark under ``bench/`` (its ``obs.*`` scopes), not here.

Artifact: a :class:`repro.obs.RunReport` JSONL with the drained
counters — render it with ``python -m repro.obs.report``. Runs over
every chip of a chip host, or with ``JAX_PLATFORMS=cpu`` over 8 forced
host devices:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.bench_obs --n 50000
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from repro.launch.runtime import default_shards, force_host_devices


def _steady_s_per_slot(engines, n: int, p: int, slots: int, repeats: int = 5):
    """Steady-state seconds per super-tick for each engine, measured
    **interleaved**: all engines are warmed (compile + burn-in) first, then
    the timed ``slots``-long advances alternate engine-by-engine across
    ``repeats`` rounds (best-of). Alternation matters for the overhead
    comparison — back-to-back blocks would let any machine-load drift land
    entirely on one side and masquerade as telemetry cost."""
    states = []
    for engine in engines:
        state = engine.init_state(np.zeros((n, p)))
        state = engine.advance(state, slots)
        state.Theta.block_until_ready()
        states.append(state)
    best = [float("inf")] * len(engines)
    for _ in range(repeats):
        for i, engine in enumerate(engines):
            t0 = time.time()
            states[i] = engine.advance(states[i], slots)
            states[i].Theta.block_until_ready()
            best[i] = min(best[i], (time.time() - t0) / slots)
    return best


def run(
    n: int = 200_000,
    p: int = 8,
    m: int = 4,
    shards: int = 8,
    slots: int = 6,
    slot_wakes: float = 2048.0,
    seed: int = 0,
    exchange: str = "auto",
    report_out: str = "results/obs_runreport.jsonl",
    verbose: bool = True,
):
    """Measure telemetry overhead; write the drained run's report."""
    import jax

    from benchmarks.bench_sparse_scale import _make_problem
    from repro.core.mixing import ExchangeSpec
    from repro.sim import CDUpdate, ShardedAsyncEngine

    if len(jax.devices()) < shards:
        raise RuntimeError(
            f"need {shards} devices (have {len(jax.devices())}); on the CPU "
            "run main() under JAX_PLATFORMS=cpu, which forces them"
        )

    rng = np.random.default_rng(seed)
    graph, obj = _make_problem(n, p, m, rng)
    spec = ExchangeSpec.from_string(exchange)
    kw = dict(
        num_shards=shards,
        relabel="rcm",
        exchange=spec,
        slot_wakes=slot_wakes,
        seed=seed,
    )
    eng_off = ShardedAsyncEngine(CDUpdate(obj), **kw)
    # Reuse the partition: identical cut, so the timed programs differ only
    # by the metrics leaves.
    eng_on = ShardedAsyncEngine(CDUpdate(obj), partition=eng_off.part, metrics=True, **kw)

    t_off, t_on = _steady_s_per_slot((eng_off, eng_on), n, p, slots)
    overhead_pct = 100.0 * (t_on - t_off) / max(t_off, 1e-12)
    rows = [
        (
            "obs_overhead",
            overhead_pct,
            f"metrics-on super-tick overhead % (on {t_on * 1e6:.4g}us, "
            f"off {t_off * 1e6:.4g}us, n={n} S={shards}; target <=5%)",
        )
    ]

    # Drained run -> RunReport.
    result = eng_on.run(
        np.zeros((n, p)), slots, metrics_every=max(slots // 2, 1)
    )
    d = os.path.dirname(report_out)
    if d:
        os.makedirs(d, exist_ok=True)
    result.report.to_jsonl(report_out)
    rows += result.report.bench_rows()

    if verbose:
        for name, v, note in rows:
            print(f"{name},{v:.4g},{note}")
        print(f"# report: {report_out}", file=sys.stderr)
    return rows


def main(argv=None):
    """CLI entry point; forces host-platform devices when still possible."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count (default: 8 on a JAX_PLATFORMS=cpu run, else every device)")
    ap.add_argument("--slots", type=int, default=6)
    ap.add_argument("--slot-wakes", type=float, default=2048.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--exchange", default="auto",
                    help="ExchangeSpec string: method[:dtype[:ef]]")
    ap.add_argument("--report-out", default="results/obs_runreport.jsonl")
    args = ap.parse_args(argv)
    shards = args.shards or default_shards(8)
    force_host_devices(shards)
    run(
        n=args.n,
        shards=shards,
        slots=args.slots,
        slot_wakes=args.slot_wakes,
        seed=args.seed,
        exchange=args.exchange,
        report_out=args.report_out,
    )


if __name__ == "__main__":
    main()
