"""Observability layer: in-jit metrics, host spans, run reports.

The load-bearing invariant is that telemetry is *free of side effects*:
metrics-on must leave Theta bit-exact versus metrics-off under forced
wakes, on both engines and both wire formats (the counters only
re-reduce values the slot already computed — no extra PRNG draws, no
Theta writes). On top of that: counter semantics against host-side
ground truth (churn schedule, DP accountant), the run driver's and the
serving tier's spans in a profiler trace and the serving counters at the
same boundaries, the ``obs.row_gather`` scope in the super-tick's HLO,
the report JSONL round-trip and CLI, the
once-per-process ExchangeSpec string deprecation, and the
BENCH_summary sync guard. Multi-shard (S=4) parity and counters run in
an 8-host-device subprocess, ``test_spmd.py`` style."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AgentData, DPConfig, knn_graph, make_objective
from repro.obs import (
    MetricsSpec,
    RunReport,
    merge_bench_summary,
    summarize_counters,
)
from repro.sim import (
    AsyncEngine,
    CDUpdate,
    ChurnConfig,
    DPCDUpdate,
    EngineConfig,
    ExchangeSpec,
    Scenario,
    ShardedAsyncEngine,
)

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _quad_problem(n, p=4, m=3, seed=0, mu=0.5, clip=None):
    rng = np.random.default_rng(seed)
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "quadratic", mu=mu, mix_mode="sparse", clip=clip)


# -- spec / config plumbing --------------------------------------------------


def test_metrics_spec_coerce():
    assert MetricsSpec.coerce(None) is None
    assert MetricsSpec.coerce(False) is None
    assert MetricsSpec.coerce(True) == MetricsSpec()
    spec = MetricsSpec(staleness=False)
    assert MetricsSpec.coerce(spec) is spec
    with pytest.raises(TypeError):
        MetricsSpec.coerce("yes")
    assert EngineConfig(metrics=True).metrics_spec() == MetricsSpec()
    assert EngineConfig().metrics_spec() is None


def test_metrics_off_engine_refuses_snapshot_and_drain():
    obj = _quad_problem(n=24)
    eng = AsyncEngine(CDUpdate(obj), seed=0)
    state = eng.init_state(np.zeros((obj.n, obj.p)))
    with pytest.raises(ValueError, match="metrics"):
        eng.metrics_snapshot(state)
    with pytest.raises(ValueError, match="metrics"):
        eng.run(np.zeros((obj.n, obj.p)), slots=2, metrics_every=1)


# -- bit-exactness: metrics must not perturb the dynamics --------------------


def test_async_forced_wakes_bit_exact_metrics_on_vs_off():
    obj = _quad_problem(n=40, seed=1)
    n, p = obj.n, obj.p
    eng_off = AsyncEngine(CDUpdate(obj), slot_wakes=40.0, seed=0, dtype=jnp.float64)
    eng_on = AsyncEngine(
        CDUpdate(obj), slot_wakes=40.0, seed=0, dtype=jnp.float64, metrics=True
    )
    s_off = eng_off.init_state(np.zeros((n, p)))
    s_on = eng_on.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(7)
    total = 0
    for _ in range(8):
        mask = rng.random(n) < 0.3
        total += int(mask.sum())
        s_off = eng_off.step(s_off, mask)
        s_on = eng_on.step(s_on, mask)
    np.testing.assert_array_equal(np.asarray(s_off.Theta), np.asarray(s_on.Theta))
    counters, _ = eng_on.metrics_snapshot(s_on)
    # slot_wakes=n makes the batch cover every forced wake: nothing dropped,
    # every realized wake applied, and each application binned by staleness.
    assert int(counters["wakes_capacity_dropped"]) == 0
    assert int(counters["wakes_realized"]) == total == int(s_on.applied)
    assert int(counters["wakes_applied"]) == total
    assert int(counters["staleness_hist"].sum()) == total


@pytest.mark.parametrize(
    "spec",
    [ExchangeSpec(), ExchangeSpec(method="all_gather", dtype="bf16", error_feedback=True)],
    ids=["f32", "bf16_ef"],
)
def test_sharded_forced_wakes_bit_exact_metrics_on_vs_off(spec):
    obj = _quad_problem(n=40, seed=2)
    n, p = obj.n, obj.p
    kw = dict(num_shards=1, slot_wakes=40.0, seed=0, exchange=spec)
    eng_off = ShardedAsyncEngine(CDUpdate(obj), **kw)
    eng_on = ShardedAsyncEngine(CDUpdate(obj), metrics=True, **kw)
    s_off = eng_off.init_state(np.zeros((n, p)))
    s_on = eng_on.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(3)
    for _ in range(6):
        mask = rng.random(n) < 0.3
        s_off = eng_off.step(s_off, mask)
        s_on = eng_on.step(s_on, mask)
    np.testing.assert_array_equal(eng_off.global_theta(s_off), eng_on.global_theta(s_on))
    counters, _ = eng_on.metrics_snapshot(s_on)
    assert int(counters["wakes_applied"].sum()) == int(np.asarray(s_on.applied).sum())
    if spec.dtype != "f32":
        # The quantized wire reports its per-slot error energy (a gauge of
        # the published-border quantization; exact value is wire-dependent,
        # presence and finiteness are the contract).
        assert np.isfinite(counters["quant_err_sq"]).all()


def test_sampled_advance_bit_exact_metrics_on_vs_off():
    obj = _quad_problem(n=48, seed=3)
    n, p = obj.n, obj.p
    scenario = Scenario(churn=ChurnConfig(leave_prob=0.05, rejoin_prob=0.3))
    eng_off = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=5, scenario=scenario)
    eng_on = AsyncEngine(
        CDUpdate(obj), slot_wakes=8.0, seed=5, scenario=scenario, metrics=True
    )
    s_off = eng_off.advance(eng_off.init_state(np.zeros((n, p))), 9)
    s_on = eng_on.advance(eng_on.init_state(np.zeros((n, p))), 9)
    np.testing.assert_array_equal(np.asarray(s_off.Theta), np.asarray(s_on.Theta))
    np.testing.assert_array_equal(np.asarray(s_off.active), np.asarray(s_on.active))


# -- counter semantics vs host-side ground truth -----------------------------


def test_churn_departures_match_schedule():
    """A deterministic departure schedule (leave_prob=1 on a chosen subset,
    no rejoins): the telemetry must count exactly those agents, once."""
    obj = _quad_problem(n=40, seed=4)
    n, p = obj.n, obj.p
    leavers = np.zeros(n)
    leavers[:17] = 1.0  # the schedule: agents 0..16 depart on slot 1
    scenario = Scenario(churn=ChurnConfig(leave_prob=leavers, rejoin_prob=0.0))
    eng = AsyncEngine(
        CDUpdate(obj), slot_wakes=8.0, seed=0, scenario=scenario, metrics=True
    )
    state = eng.advance(eng.init_state(np.zeros((n, p))), 5)
    counters, _ = eng.metrics_snapshot(state)
    assert int(counters["churn_departures"]) == 17
    assert int(counters["churn_rejoins"]) == 0
    # Cross-check against the engine's own churn state.
    assert int(np.asarray(state.active).sum()) == n - 17

    engS = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, slot_wakes=8.0, seed=0,
        scenario=scenario, metrics=True,
    )
    stS = engS.advance(engS.init_state(np.zeros((n, p))), 5)
    countersS, _ = engS.metrics_snapshot(stS)
    assert int(countersS["churn_departures"].sum()) == 17
    assert int(countersS["churn_rejoins"].sum()) == 0


def test_dp_budget_stopped_matches_accountant():
    """The dp_budget_stopped gauge equals the host accountant's count, and
    the derived eps-spent matches DPCDUpdate.eps_spent, on both engines."""
    obj = _quad_problem(n=48, seed=3, clip=1.0)
    n, p = obj.n, obj.p
    planned_Ti = 3
    dp = DPCDUpdate.plan(obj, DPConfig(eps_bar=1.0), planned_Ti=planned_Ti)
    for eng in (
        AsyncEngine(dp, slot_wakes=48.0, seed=0, metrics=True),
        ShardedAsyncEngine(dp, num_shards=1, slot_wakes=48.0, seed=0, metrics=True),
    ):
        state = eng.init_state(np.zeros((n, p)))
        for k in range(planned_Ti + 2):
            state = eng.step(state, np.ones(n, bool))
            counters, derived = eng.metrics_snapshot(state)
            gauge = int(np.asarray(counters["dp_budget_stopped"]).sum())
            ustate = state.ustate
            if isinstance(eng, ShardedAsyncEngine):
                ustate = eng.part.unpad_rows(np.asarray(ustate))
            assert gauge == dp.budget_stopped(ustate), (type(eng).__name__, k)
        # slot_wakes=n gives every forced wake batch room: after
        # planned_Ti + 2 all-wake slots every agent has spent its budget.
        assert gauge == n
        np.testing.assert_allclose(
            derived["dp_eps_spent_max"], dp.eps_spent(np.asarray(ustate)).max()
        )


def test_exchange_counters_accumulate_per_slot_volume():
    """Sharded exchange counters advance by the partition's static
    per-slot volume each super-tick (padded rows included: static shapes
    ship them)."""
    obj = _quad_problem(n=40, seed=6)
    n, p = obj.n, obj.p
    eng = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, slot_wakes=8.0, seed=0, metrics=True
    )
    steps = 4
    state = eng.init_state(np.zeros((n, p)))
    for _ in range(steps):
        state = eng.step(state, np.ones(n, bool))
    counters, _ = eng.metrics_snapshot(state)
    xrows = eng.part.exchange_rows(eng.exchange_method)
    xbytes = xrows * eng.exchange_spec.payload_bytes_per_row(p)
    assert int(counters["exchange_rows"].sum()) == steps * xrows
    assert float(counters["exchange_bytes"].sum()) == float(steps * xbytes)


# -- host spans and serving counters -----------------------------------------


def _host_spans(tmp_path, fn):
    """Run ``fn`` under a profiler trace; return its result and the
    ``repro.*`` host spans as ``(name, thread, start_ns, end_ns)``, read
    back with ``jax.profiler.ProfileData``, in order of start."""
    import glob

    import jax
    from jax.profiler import ProfileData

    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    spans = [
        (ev.name, line.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("repro.")
    ]
    return out, sorted(spans, key=lambda s: (s[2], -s[3]))


def _inside(inner, outer) -> bool:
    return inner[1] == outer[1] and outer[2] <= inner[2] and inner[3] <= outer[3]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_place_tables_span_at_set_up(tmp_path, dynamic):
    """A static-topology engine places its tables in one
    ``repro.engine.place_tables`` span at set-up, and its gauges read no
    table placed off the default on the CPU; the dynamic-topology engine
    rebuilds its tiles on every swap and places nothing."""
    from repro.sim import GraphUpdate

    obj = _quad_problem(n=32, seed=3)
    kw = dict(graph_update=GraphUpdate(every=4)) if dynamic else {}
    eng, spans = _host_spans(
        tmp_path, lambda: AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0, **kw)
    )
    assert len(_named(spans, "repro.engine.place_tables")) == (0 if dynamic else 1)
    meta = eng.report_meta()
    assert (meta["static_tables_relaid"], meta["static_relaid_bytes"]) == (0, 0)


@pytest.mark.parametrize("sharded", [False, True], ids=["async", "sharded_s1"])
def test_run_spans_nest_in_repro_run(tmp_path, sharded):
    """One ``run(..., snapshot_every=, serve=)`` call: one
    ``repro.run.advance`` per chunk, one ``repro.serve.publish`` per
    publication (in its ``repro.run.publish``, holding one
    ``repro.serve.publish.sync``) and one ``repro.run.result``, all
    nested in the call's one ``repro.run``."""
    from repro.serve import ServeHandle

    obj = _quad_problem(n=40, seed=11)
    kw = dict(slot_wakes=8.0, seed=0)
    eng = (
        ShardedAsyncEngine(CDUpdate(obj), num_shards=1, **kw)
        if sharded
        else AsyncEngine(CDUpdate(obj), **kw)
    )
    handle = ServeHandle.for_engine(eng)
    theta0 = np.zeros((obj.n, obj.p))
    eng.run(theta0, slots=12, snapshot_every=4, serve=handle)  # compile untraced
    c0 = handle.counters()
    res, spans = _host_spans(
        tmp_path, lambda: eng.run(theta0, slots=12, snapshot_every=4, serve=handle)
    )
    assert res.slots == 12
    (run,) = _named(spans, "repro.run")
    assert all(_inside(s, run) for s in spans)
    advance = _named(spans, "repro.run.advance")
    assert len(advance) == 3  # 12 slots in chunks of the snapshot period
    publish = _named(spans, "repro.serve.publish")
    published = handle.counters()["serve_snapshots_published"] - c0["serve_snapshots_published"]
    assert len(publish) == published == 4  # at the start and after each chunk
    events = _named(spans, "repro.run.publish")
    for pub, event in zip(publish, events, strict=True):
        assert _inside(pub, event)
        (sync,) = [s for s in _named(spans, "repro.serve.publish.sync") if _inside(s, pub)]
    assert len(_named(spans, "repro.serve.publish.sync")) == 4
    (result,) = _named(spans, "repro.run.result")
    assert result[2] >= max(s[3] for s in advance + events)


def test_dynamic_run_spans_topology_and_events(tmp_path):
    """The segment driver: a graph refresh in ``repro.run.topology``, each
    periodic callback in its ``repro.run.<event>`` span."""
    from repro.sim import GraphUpdate

    obj = _quad_problem(n=40, seed=12)
    eng = AsyncEngine(
        CDUpdate(obj),
        slot_wakes=8.0,
        seed=0,
        metrics=True,
        graph_update=GraphUpdate(every=4, k=5, candidates=4, gamma=2.0, seed=1),
    )
    theta0 = np.zeros((obj.n, obj.p))
    kw = dict(slots=8, record_every=4, metrics_every=8)
    eng.run(theta0, **kw)
    res, spans = _host_spans(tmp_path, lambda: eng.run(theta0, **kw))
    (run,) = _named(spans, "repro.run")
    assert all(_inside(s, run) for s in spans)
    assert len(_named(spans, "repro.run.topology")) == 1  # the refresh at slot 4
    assert len(_named(spans, "repro.run.record")) == 2 == len(res.objective) - 1
    assert len(_named(spans, "repro.run.drain")) == 1 == len(res.report.snapshots)
    assert len(_named(spans, "repro.run.advance")) >= 2
    assert len(_named(spans, "repro.run.result")) == 1


@pytest.mark.parametrize("call", ["predict", "rows"])
def test_serving_spans_in_order_and_counters(tmp_path, call):
    """A request: ``route``, ``score`` and ``fetch`` in that order inside
    ``repro.serve.predict``; the counters at those boundaries grow, and
    the publication's sync is part of its time."""
    from repro.serve import ServeHandle

    obj = _quad_problem(n=40, seed=13)
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0)
    handle = ServeHandle.for_engine(eng)
    eng.run(np.zeros((obj.n, obj.p)), slots=8, snapshot_every=4, serve=handle)
    ids = np.array([3, 7, 7, 21])
    args = (ids, np.ones((ids.size, obj.p))) if call == "predict" else (ids,)
    getattr(handle, call)(*args)  # compile untraced
    c0 = handle.counters()
    _, spans = _host_spans(tmp_path, lambda: getattr(handle, call)(*args))
    c1 = handle.counters()
    (req,) = _named(spans, "repro.serve.predict")
    parts = [s for s in spans if s is not req]
    assert [s[0] for s in parts] == [
        "repro.serve.route", "repro.serve.score", "repro.serve.fetch",
    ]
    assert all(_inside(s, req) for s in parts)
    assert all(a[3] <= b[2] for a, b in zip(parts, parts[1:]))
    assert c1["serve_requests"] == c0["serve_requests"] + 1
    for name in ("serve_route_s_total", "serve_fetch_s_total"):
        grown = c1[name] - c0[name]
        assert 0.0 < grown <= (req[3] - req[2]) * 1e-9
    assert 0.0 < c1["serve_publish_sync_s_total"] <= c1["serve_publish_s_total"]


@pytest.mark.parametrize("variant", ["static", "delay", "sharded", "dynamic"])
def test_supertick_hlo_carries_row_gather_scope(variant):
    """The woken rows' constant gather is its own ``obs.row_gather``
    scope in the compiled super-tick, on every slot path."""
    from repro.sim import DelayConfig, GraphUpdate

    obj = _quad_problem(n=32, seed=14)
    kw = dict(slot_wakes=8.0, seed=0)
    if variant == "sharded":
        eng = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, **kw)
        chunk, tables = eng._chunk, eng._static
    elif variant == "dynamic":
        eng = AsyncEngine(CDUpdate(obj), graph_update=GraphUpdate(every=4), **kw)
        chunk, tables = eng._chunk_dyn, eng._dyn
    else:
        if variant == "delay":
            kw["scenario"] = Scenario(delay=DelayConfig(max_delay=2))
        eng = AsyncEngine(CDUpdate(obj), **kw)
        chunk, tables = eng._chunk, eng._static
    state = eng.init_state(np.zeros((obj.n, obj.p)))
    text = chunk.lower(state, tables, 2).compile().as_text()
    assert "obs.row_gather" in text
    assert "obs.row_update" in text


def test_spans_keep_nothing_without_a_profiler():
    """With no trace running, the spans of many runs and requests leave
    nothing behind: no allocation made under ``repro.obs.trace`` is alive
    after 40 calls that opened about 1,600 spans."""
    import gc
    import tracemalloc

    from repro.obs import trace
    from repro.serve import ServeHandle

    obj = _quad_problem(n=40, seed=15)
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0)
    handle = ServeHandle.for_engine(eng)
    theta0 = np.zeros((obj.n, obj.p))
    ids, X = np.arange(4), np.ones((4, obj.p))

    def calls(k):
        # Each call opens 1 + 8 advance + 9 x 3 publication + 1 result
        # + 4 request spans: 41.
        for _ in range(k):
            eng.run(theta0, slots=8, snapshot_every=1, serve=handle)
            handle.predict(ids, X)

    calls(2)  # compile untraced
    tracemalloc.start(64)
    try:
        calls(40)
        gc.collect()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    kept = snap.filter_traces([tracemalloc.Filter(True, trace.__file__, all_frames=True)])
    assert sum(st.size for st in kept.statistics("filename")) == 0


def test_compile_cache_keeps_each_programs_scopes(tmp_path):
    """Two programs that differ only in their ``obs.*`` scope do not share
    an executable through the persistent compilation cache: the second is
    compiled with its own scope, not loaded with the first's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import repro.obs.trace  # noqa: F401  (keys the cache on metadata)

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0 + 1.0
        return jax.jit(f)

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    prev = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        x = jnp.ones(8)
        first = program("obs.first_scope").lower(x).compile().as_text()
        assert "obs.first_scope" in first and any(tmp_path.iterdir())
        second = program("obs.second_scope").lower(x).compile().as_text()
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert "obs.second_scope" in second
    assert "obs.first_scope" not in second


# -- run reports -------------------------------------------------------------


def test_run_metrics_every_drains_and_reports():
    obj = _quad_problem(n=40, seed=9)
    n, p = obj.n, obj.p
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0, metrics=True)
    res = eng.run(np.zeros((n, p)), slots=12, metrics_every=4, record_every=6)
    assert len(res.report.snapshots) == 3
    assert res.report.meta["engine"] == "AsyncEngine"
    assert len(res.objective) == 3  # initial + record_every at slots 6, 12
    # Drains are cumulative reads of the same accumulator: monotone.
    applied = [s["counters"]["wakes_applied"] for s in res.report.snapshots]
    assert applied == sorted(applied)
    assert applied[-1] == int(np.asarray(res.state.applied).sum())
    # And the drain must not perturb the dynamics.
    plain = eng.run(np.zeros((n, p)), slots=12)
    np.testing.assert_array_equal(plain.Theta, res.Theta)


def test_report_jsonl_roundtrip_and_bench_rows(tmp_path):
    report = RunReport(meta={"engine": "AsyncEngine", "n": 8})
    report.add_snapshot(
        2,
        {"wakes_applied": np.int64(5), "staleness_hist": np.array([3, 2])},
        derived={"dp_eps_spent_max": np.float64(0.5)},
    )
    path = tmp_path / "report.jsonl"
    report.to_jsonl(str(path))
    back = RunReport.from_jsonl(str(path))
    assert back.meta == {"engine": "AsyncEngine", "n": 8}
    assert back.snapshots == report.snapshots
    rows = dict((name, v) for name, v, _ in back.bench_rows())
    assert rows["obs_wakes_applied"] == 5.0
    assert "staleness_hist" not in rows  # vectors render in the table, not rows


def test_report_cli_renders_merges_and_validates(tmp_path, capsys):
    from repro.obs import report as report_cli

    report = RunReport(meta={"engine": "AsyncEngine"})
    report.add_snapshot(1, {"wakes_applied": np.int64(3)})
    rpath = tmp_path / "r.jsonl"
    report.to_jsonl(str(rpath))
    bench = tmp_path / "BENCH_summary.json"
    merge_bench_summary(str(bench), [("existing_row", 1.0, "kept")])

    rc = report_cli.main([str(rpath), "--merge-bench", str(bench)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "wakes_applied" in out
    merged = json.loads(bench.read_text())
    assert merged["obs_wakes_applied"]["us_per_call"] == 3.0
    assert merged["existing_row"]["us_per_call"] == 1.0  # merge, not clobber

    with pytest.raises(SystemExit):
        report_cli.main([])  # nothing to do


# -- satellites: warning dedup, bench sync, run.py CLI -----------------------


def test_exchange_string_deprecation_warns_once_per_process(monkeypatch):
    import repro.core.mixing as mixing

    obj = _quad_problem(n=24, seed=10)
    # Restored after the test, so a later test in this process that
    # expects the first warning still sees it.
    monkeypatch.setattr(mixing, "_warned_bare_exchange_string", False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            ShardedAsyncEngine(CDUpdate(obj), num_shards=1, seed=0, exchange="p2p")
    dep = [
        w for w in caught
        if issubclass(w.category, DeprecationWarning) and "bare string" in str(w.message)
    ]
    assert len(dep) == 1, [str(w.message) for w in caught]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_bench_sync(tmp_path):
    sync = _load_tool("check_bench_sync")
    root = tmp_path / "BENCH_summary.json"
    results = tmp_path / "results" / "BENCH_summary.json"
    results.parent.mkdir()
    assert sync.check(root, results) == []  # neither exists: nothing to flag
    root.write_text(json.dumps({"a": {"us_per_call": 1.0, "derived": ""}}))
    errors = sync.check(root, results)
    assert len(errors) == 1 and "counterpart" in errors[0]
    results.write_text(root.read_text())
    assert sync.check(root, results) == []
    results.write_text(json.dumps({"a": {"us_per_call": 2.0, "derived": ""}}))
    assert any("differs" in e for e in sync.check(root, results))
    results.write_text(json.dumps({"b": {"us_per_call": 1.0, "derived": ""}}))
    assert len(sync.check(root, results)) == 2  # 'a' and 'b' each one-sided


def _run_benchrun(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.join(REPO_ROOT, "src"), env.get("PYTHONPATH", "")])
    )
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=120,
    )


def test_benchmarks_run_list_and_unknown_only():
    listed = _run_benchrun(["--list"])
    assert listed.returncode == 0
    names = listed.stdout.split()
    assert "obs" in names and "sharded_engine" in names
    bogus = _run_benchrun(["--only", "definitely_not_a_bench"])
    assert bogus.returncode != 0
    assert "definitely_not_a_bench" in bogus.stderr
    for name in names:
        assert name in bogus.stderr  # the error lists every valid name


# -- multi-shard metrics: 8-host-device subprocess ---------------------------

OBS_MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import AgentData, DPConfig, knn_graph, make_objective
    from repro.sim import (AsyncEngine, CDUpdate, DPCDUpdate, ExchangeSpec,
                           ShardedAsyncEngine)

    assert len(jax.devices()) == 8

    def quad(n, p=4, m=3, seed=0, clip=None):
        rng = np.random.default_rng(seed)
        graph = knn_graph(rng.normal(size=(n, 8)), k=8)
        targets = rng.normal(size=(n, p)) / np.sqrt(p)
        X = rng.normal(size=(n, m, p)) / np.sqrt(p)
        y = np.einsum("nmp,np->nm", X, targets)
        data = AgentData(X=X, y=y, mask=np.ones((n, m)))
        return make_objective(graph, data, "quadratic", mu=0.5,
                              mix_mode="sparse", clip=clip)

    # 1) S=4 forced-wake parity metrics-on vs metrics-off, f32 p2p and
    #    the compressed bf16+EF wire; counters match host ground truth.
    obj = quad(64, seed=1)
    n, p = obj.n, obj.p
    masks = [np.random.default_rng(5).random(n) < 0.3 for _ in range(6)]
    for spec in (ExchangeSpec(method="p2p"),
                 ExchangeSpec(method="p2p", dtype="bf16", error_feedback=True)):
        kw = dict(num_shards=4, relabel="rcm", slot_wakes=64.0, seed=0,
                  exchange=spec)
        eng_off = ShardedAsyncEngine(CDUpdate(obj), **kw)
        eng_on = ShardedAsyncEngine(CDUpdate(obj), metrics=True, **kw)
        s_off = eng_off.init_state(np.zeros((n, p)))
        s_on = eng_on.init_state(np.zeros((n, p)))
        for mask in masks:
            s_off = eng_off.step(s_off, mask)
            s_on = eng_on.step(s_on, mask)
        assert np.array_equal(eng_off.global_theta(s_off),
                              eng_on.global_theta(s_on)), spec
        counters, _ = eng_on.metrics_snapshot(s_on)
        assert int(counters["wakes_applied"].sum()) == int(
            np.asarray(s_on.applied).sum())
        xrows = eng_on.part.exchange_rows(eng_on.exchange_method)
        assert int(counters["exchange_rows"].sum()) == len(masks) * xrows
        assert counters["p2p_rows_by_offset"].shape[-1] > 0
        if spec.dtype != "f32":
            assert np.isfinite(counters["quant_err_sq"]).all()
    print("S4_PARITY_OK")

    # 2) S=4 DP budget-stop gauge == host accountant.
    objc = quad(48, seed=3, clip=1.0)
    dp = DPCDUpdate.plan(objc, DPConfig(eps_bar=1.0), planned_Ti=3)
    eng = ShardedAsyncEngine(dp, num_shards=4, relabel="rcm", slot_wakes=48.0,
                             seed=0, metrics=True)
    st = eng.init_state(np.zeros((objc.n, objc.p)))
    for _ in range(5):
        st = eng.step(st, np.ones(objc.n, bool))
    counters, derived = eng.metrics_snapshot(st)
    counts = eng.part.unpad_rows(np.asarray(st.ustate))
    gauge = int(np.asarray(counters["dp_budget_stopped"]).sum())
    assert gauge == dp.budget_stopped(counts) == objc.n, gauge
    np.testing.assert_allclose(derived["dp_eps_spent_max"],
                               dp.eps_spent(counts).max())
    print("S4_DP_OK")

    # 3) Drained run + report on the 8-shard engine: the CI obs lane's
    #    in-test twin.
    eng8 = ShardedAsyncEngine(CDUpdate(obj), num_shards=8, relabel="rcm",
                              slot_wakes=16.0, seed=0, metrics=True)
    res = eng8.run(np.zeros((n, p)), slots=6, metrics_every=3)
    assert len(res.report.snapshots) == 2
    res.report.to_jsonl("obs_report_test.jsonl")
    from repro.obs import RunReport
    back = RunReport.from_jsonl("obs_report_test.jsonl")
    assert back.meta["num_shards"] == 8
    print("S8_REPORT_OK")
    """
)


def test_obs_multidevice_parity_counters_and_report(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env.pop("JAX_ENABLE_X64", None)
    res = subprocess.run(
        [sys.executable, "-c", OBS_MULTIDEV_SCRIPT],
        env=env, capture_output=True, text=True, timeout=900, cwd=str(tmp_path),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("S4_PARITY_OK", "S4_DP_OK", "S8_REPORT_OK"):
        assert marker in res.stdout
