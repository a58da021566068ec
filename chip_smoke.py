#!/usr/bin/env python3
"""Smoke run of the engine's main path on a TPU, through the user entry points.

    python3 chip_smoke.py              # one chip: the three phases below
    python3 chip_smoke.py --chips 4    # four chips: the sharded engine only

One chip, one process, in order:

1. ``engine_cd`` — ``make_engine(CDUpdate(obj), EngineConfig(...))`` on a
   deployment of 1,000,000 agents (p = 20 features, m = 16 points each, a
   random geometric graph of average degree 16, churn on), driven by
   ``engine.run(..., snapshot_every=, serve=ServeHandle.for_engine(engine))``
   with ``predict`` batches against the published snapshots. Fails unless
   the objective falls and every served row equals the trainer's Theta at
   the served version.
2. ``engine_dpcd`` — a few slots of the same deployment under ``DPCDUpdate``
   with a per-agent budget. Fails if any agent spends more than its budget.
3. ``fused_kernel`` — at n = 4096 ``fused="auto"`` must engage the fused
   Pallas kernel, compiled (the program holds a ``tpu_custom_call``), and
   agree with the unfused engine under forced wakes to 1e-6.

With ``--chips 4``: ``sharded_engine`` (S = 4 with RCM relabel and the p2p
halo exchange at four times the one-chip n, every shard's state and tiles
placed on its own device; the objective, which no chip can hold, is
reduced on the host; fails unless it falls, served rows match, and each
device's peak bytes stay within its share plus the program's working
memory), then ``sharded_parity`` (the same engine at S = 4 against S = 1
under forced wakes, n = 262,144, to 1e-5).

Each phase prints one ``phase {...}`` line of smoke figures (compile and
steady seconds, applied wakes, objective before and after, DP spend,
served-row mismatches, ``peak_bytes_in_use``, fused kernel engaged); none
is a benchmark metric. Every compiled super-tick is checked for f64. The
last line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed; with no TPU the script exits 1 before any phase.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

P, M, AVG_DEGREE = 20, 16, 16.0  # the paper's ALS width, points per agent
ONE_CHIP_N = 1_000_000


class SmokeFailure(RuntimeError):
    """A phase's result is wrong."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **figures) -> None:
    print("phase " + json.dumps({"phase": phase, **figures}), flush=True)


@contextlib.contextmanager
def compile_seconds():
    """Sum of JAX's trace, lowering and backend-compile durations inside the
    block, and the persistent compile-cache hits (a hit records its
    retrieval, not a compile)."""
    import jax

    box = {"s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            box["s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            box["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def assert_no_f64(compiled_text: str, what: str) -> None:
    check(re.search(r"\bf64\[", compiled_text) is None, f"f64 in the compiled {what}")


def peak_bytes(devices) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def make_deployment(n: int, seed: int, clip=None):
    """Quadratic objective over a random geometric graph, data from ``seed``."""
    import numpy as np

    from repro.core import AgentData, make_objective, random_geometric_graph

    rng = np.random.default_rng(seed)
    graph = random_geometric_graph(n, rng, avg_degree=AVG_DEGREE)
    targets = rng.standard_normal((n, P), dtype=np.float32) / np.sqrt(P)
    X = rng.standard_normal((n, M, P), dtype=np.float32) / np.float32(np.sqrt(P))
    y = np.einsum("nmp,np->nm", X, targets) + 0.1 * rng.standard_normal((n, M), np.float32)
    data = AgentData(X=X, y=y, mask=np.ones((n, M), np.float32))
    return make_objective(graph, data, "quadratic", mu=0.5, clip=clip, mix_mode="sparse")


def churn_config(slot_wakes: float, seed: int):
    from repro.sim import ChurnConfig, EngineConfig, Scenario

    return EngineConfig(
        slot_wakes=slot_wakes,
        scenario=Scenario(churn=ChurnConfig(leave_prob=0.01, rejoin_prob=0.2)),
        seed=seed,
    )


def serve_check(handle, result, theta_of, n: int, rng) -> tuple[int, int]:
    """Served rows and predictions against the trainer's Theta at the served
    version. Returns (row mismatches, requests)."""
    import numpy as np

    snap = handle.snapshot()
    check(snap.version == result.slots, f"served version {snap.version} != slot {result.slots}")
    theta = theta_of(result)
    mismatches, requests = 0, 0
    for _ in range(3):
        ids = rng.choice(n, size=4096, replace=False)
        rows = handle.rows(ids, at=snap)
        mismatches += int((~np.all(rows.values == theta[ids], axis=1)).sum())
        Xq = rng.standard_normal((ids.size, P), dtype=np.float32)
        scores = handle.predict(ids, Xq, at=snap)
        want = np.einsum("bp,bp->b", theta[ids].astype(np.float64), Xq)
        check(
            np.allclose(scores.values, want, rtol=1e-5, atol=1e-5),
            "predict scores disagree with the trainer's rows",
        )
        requests += 2
    return mismatches, requests


def run_engine_phase(name, engine, obj, n, seed, objective_of, theta_of, devices, extra=None):
    """Warm-up run (compiles), then a timed steady run, both serving."""
    import numpy as np

    from repro.serve import ServeHandle

    rng = np.random.default_rng(seed + 1)
    handle = ServeHandle.for_engine(engine)
    state0 = engine.init_state(np.zeros((n, P), np.float32))
    q0 = objective_of(state0)
    every = 16
    with compile_seconds() as comp:
        warm = engine.run(None, every, state=state0, snapshot_every=every, serve=handle)
    mism, reqs = serve_check(handle, warm, theta_of, n, rng)
    t0 = time.perf_counter()
    res = engine.run(None, 4 * every, state=warm.state, snapshot_every=every, serve=handle)
    steady_s = time.perf_counter() - t0
    m2, r2 = serve_check(handle, res, theta_of, n, rng)
    mism, reqs = mism + m2, reqs + r2
    q1 = objective_of(res.state)
    compiled = engine._chunk.lower(res.state, engine._static, every).compile()
    assert_no_f64(compiled.as_text(), f"{name} super-tick")
    figures = {
        "n": n,
        "compile_s": round(comp["s"], 3),
        "compile_cache_hits": comp["cache_hits"],
        "steady_s": steady_s,
        "steady_slots": 4 * every,
        "applied_wakes": res.wakes_applied,
        "dropped_wakes": res.wakes_dropped,
        "objective_before": q0,
        "objective_after": q1,
        "served_row_mismatches": mism,
        "predict_requests": reqs,
        "fused": bool(engine.fused),
        "peak_bytes_in_use": peak_bytes(devices),
        **(extra or {}),
    }
    return res, figures, compiled.memory_analysis()


def phase_engine_cd(seed: int, devices):
    import numpy as np

    from repro.sim import CDUpdate, make_engine

    t0 = time.perf_counter()
    obj = make_deployment(ONE_CHIP_N, seed)
    setup_s = time.perf_counter() - t0
    engine = make_engine(CDUpdate(obj), churn_config(8192.0, seed))
    _, fig, _ = run_engine_phase(
        "engine_cd", engine, obj, ONE_CHIP_N, seed,
        objective_of=lambda s: float(obj.value(s.Theta)),
        theta_of=lambda r: np.asarray(r.Theta),
        devices=devices,
        extra={"setup_s": setup_s, "max_degree": int(obj.graph.max_degree())},
    )
    emit("engine_cd", **fig)
    check(fig["objective_after"] < fig["objective_before"], "the objective did not fall")
    check(fig["served_row_mismatches"] == 0, "a served row differs from the trainer's Theta")
    return obj


def phase_engine_dpcd(obj, seed: int, devices) -> None:
    import numpy as np

    from repro.core import DPConfig, make_objective
    from repro.sim import DPCDUpdate, make_engine

    private = make_objective(
        obj.graph, obj.data, "quadratic", mu=obj.mu, clip=1.0, mix_mode="sparse"
    )
    cfg = DPConfig(eps_bar=1.0)
    update = DPCDUpdate.plan(private, cfg, planned_Ti=1)
    engine = make_engine(update, churn_config(8192.0, seed))
    state0 = engine.init_state(np.zeros((private.n, P), np.float32))
    q0 = float(private.value(state0.Theta))
    with compile_seconds() as comp:
        res = engine.run(None, 16, state=state0)
    t0 = time.perf_counter()
    res = engine.run(None, 16, state=res.state)
    steady_s = time.perf_counter() - t0
    counts = np.asarray(res.update_state)
    eps = update.eps_spent(counts)
    text = engine._chunk.lower(res.state, engine._static, 16).compile().as_text()
    assert_no_f64(text, "DP-CD super-tick")
    emit(
        "engine_dpcd",
        n=private.n,
        compile_s=round(comp["s"], 3),
        compile_cache_hits=comp["cache_hits"],
        steady_s=steady_s,
        steady_slots=16,
        applied_wakes=res.wakes_applied,
        objective_before=q0,
        objective_after=float(private.value(res.state.Theta)),
        eps_budget=cfg.eps_bar,
        eps_spent_max=float(eps.max()),
        eps_spent_mean=float(eps.mean()),
        budget_stopped=update.budget_stopped(counts),
        peak_bytes_in_use=peak_bytes(devices),
    )
    check(float(eps.max()) <= cfg.eps_bar * (1 + 1e-9), "an agent exceeded its DP budget")


def phase_fused_kernel(seed: int, devices) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.mixing import kernel_max_n
    from repro.sim import CDUpdate, make_engine

    n = kernel_max_n()
    obj = make_deployment(n, seed)
    cfg = churn_config(64.0, seed).replace(batch_size=512)
    fused = make_engine(CDUpdate(obj), cfg)
    plain = make_engine(CDUpdate(obj), cfg.replace(fused=False))
    check(fused.fused, f"fused='auto' did not engage the fused kernel at n={n}")
    rng = np.random.default_rng(seed + 2)
    masks = [rng.random(n) < 0.05 for _ in range(8)]
    theta0 = np.zeros((n, P), np.float32)
    sf, su = fused.init_state(theta0), plain.init_state(theta0)
    with compile_seconds() as comp:
        for mask in masks:
            sf, su = fused.step(sf, mask), plain.step(su, mask)
    err = float(np.abs(np.asarray(sf.Theta) - np.asarray(su.Theta)).max())
    forced = fused._forced.lower(sf, fused._static, jnp.asarray(masks[0])).compile().as_text()
    chunk = fused._chunk.lower(sf, fused._static, 16).compile().as_text()
    kernel_ran = "tpu_custom_call" in forced and "tpu_custom_call" in chunk
    assert_no_f64(chunk, "fused super-tick")
    sampled = fused.run(None, 32, state=sf)
    emit(
        "fused_kernel",
        n=n,
        compile_s=round(comp["s"], 3),
        compile_cache_hits=comp["cache_hits"],
        forced_slots=len(masks),
        max_abs_diff_vs_unfused=err,
        fused_kernel_compiled=kernel_ran,
        sampled_applied_wakes=sampled.wakes_applied,
        peak_bytes_in_use=peak_bytes(devices),
    )
    check(kernel_ran, "the fused kernel is not a tpu_custom_call in the compiled program")
    check(err <= 1e-6, f"fused vs unfused max |diff| {err} > 1e-6")
    check(bool(np.isfinite(sampled.Theta).all()), "non-finite Theta from the fused run")


def phase_sharded_parity(seed: int, devices) -> None:
    import numpy as np

    from repro.sim import CDUpdate, EngineConfig, ExchangeSpec, make_engine

    n = 262_144
    obj = make_deployment(n, seed)
    cfg = EngineConfig(
        slot_wakes=2048.0, batch_size=4096, seed=seed, relabel="rcm",
        exchange=ExchangeSpec(method="p2p"),
    )
    eng4 = make_engine(CDUpdate(obj), cfg, shards=4)
    eng1 = make_engine(CDUpdate(obj), cfg, shards=1)
    check(eng4.exchange_method == "p2p", "S=4 engine is not on the p2p exchange")
    rng = np.random.default_rng(seed + 3)
    masks = [rng.random(n) < 0.01 for _ in range(8)]
    theta0 = np.zeros((n, P), np.float32)
    s4, s1 = eng4.init_state(theta0), eng1.init_state(theta0)
    with compile_seconds() as comp:
        for mask in masks:
            s4, s1 = eng4.step(s4, mask), eng1.step(s1, mask)
    t4, t1 = eng4.global_theta(s4), eng1.global_theta(s1)
    err = float(np.abs(t4 - t1).max())
    emit(
        "sharded_parity",
        n=n,
        shards=4,
        compile_s=round(comp["s"], 3),
        compile_cache_hits=comp["cache_hits"],
        forced_slots=len(masks),
        max_abs_diff_vs_s1=err,
        bit_exact=bool(np.array_equal(t4, t1)),
        halo_rows_per_shard=int(eng4.part.halo.shape[1]),
        peak_bytes_in_use=peak_bytes(devices),
    )
    check(err <= 1e-5, f"S=4 vs S=1 max |diff| {err} > 1e-5")


def host_objective(obj, theta, chunk: int = 1 << 18) -> float:
    """Q(Theta) of Eq. 2 for the quadratic loss, in numpy (f64) and in row
    chunks: the objective of a population that no single chip holds."""
    import numpy as np

    theta = np.asarray(theta, np.float64)
    mix = obj.mix
    smooth = 0.0
    for lo in range(0, mix.rows.size, chunk):
        r, c = mix.rows[lo : lo + chunk], mix.cols[lo : lo + chunk]
        d2 = np.sum((theta[r] - theta[c]) ** 2, axis=1)
        smooth += 0.25 * float(np.sum(mix.vals[lo : lo + chunk] * d2))
    local = np.empty(obj.n)
    for lo in range(0, obj.n, chunk):
        sl = slice(lo, lo + chunk)
        mask = obj.data.mask[sl]
        resid = np.einsum("nmp,np->nm", obj.data.X[sl], theta[sl]) - obj.data.y[sl]
        local[sl] = np.sum(resid**2 * mask, axis=1) / np.maximum(mask.sum(axis=1), 1.0)
        local[sl] += obj.lambdas[sl] * np.sum(theta[sl] ** 2, axis=1)
    return smooth + obj.mu * float(np.sum(obj.degrees * obj.confidences * local))


def phase_sharded_engine(seed: int, devices) -> None:
    import jax
    import numpy as np

    from repro.sim import CDUpdate, ExchangeSpec, make_engine

    n = 4 * ONE_CHIP_N
    t0 = time.perf_counter()
    obj = make_deployment(n, seed)
    setup_s = time.perf_counter() - t0
    cfg = churn_config(4 * 8192.0, seed).replace(
        relabel="rcm", exchange=ExchangeSpec(method="p2p")
    )
    engine = make_engine(CDUpdate(obj), cfg, shards=4)
    # Everything the engine places across the chips (unpadded): its
    # (S, R, ...) tiles and the state stack.
    leaves = jax.tree.leaves((engine._static, engine.init_state(np.zeros((n, P), np.float32))))
    placed = sum(int(a.nbytes) for a in leaves)
    whole = [a.shape for a in leaves if a.sharding.shard_shape(a.shape)[0] * 4 != a.shape[0]]
    check(not whole, f"arrays not split one block per device: {whole}")
    res, fig, mem = run_engine_phase(
        "sharded_engine", engine, obj, n, seed,
        objective_of=lambda state: host_objective(obj, engine.global_theta(state)),
        theta_of=lambda r: r.Theta,
        devices=devices,
        extra={
            "setup_s": setup_s,
            "shards": 4,
            "rows_per_shard": int(engine.part.rows_per_shard),
            "halo_rows_per_shard": int(engine.part.halo.shape[1]),
            "exchange": engine.exchange_method,
            "placed_bytes_total": placed,
        },
    )
    # A device's bound: its share of the tiles and state with the halo maps
    # (the program's per-device arguments), the program's working memory,
    # and the states alive at once (the input, the output, and the older
    # versions the serving ring still holds).
    from repro.serve import ServeSpec

    live_states = 2 + ServeSpec().buffers
    bound = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    bound += live_states * mem.output_size_in_bytes
    fig["per_device_bound_bytes"] = int(bound)
    emit("sharded_engine", **fig)
    check(fig["objective_after"] < fig["objective_before"], "the objective did not fall")
    check(fig["served_row_mismatches"] == 0, "a served row differs from the trainer's Theta")
    peak = max(fig["peak_bytes_in_use"])
    check(peak <= bound, f"a device's peak bytes {peak} exceed its bound {bound}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform!r} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPUs, have {len(devices)}",
              file=sys.stderr)
        return 1
    devices = devices[: args.chips]
    print(f"# {len(devices)} x {devices[0].device_kind}, compile cache {cache_dir}",
          flush=True)
    if args.chips == 1:
        obj = phase_engine_cd(args.seed, devices)
        phase_engine_dpcd(obj, args.seed, devices)
        del obj
        gc.collect()
        phase_fused_kernel(args.seed, devices)
    else:
        # The sharded engine first, so each device's peak bytes are its own
        # share's; the parity phase then puts the whole S = 1 graph on one.
        phase_sharded_engine(args.seed, devices)
        gc.collect()
        phase_sharded_parity(args.seed, devices)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
