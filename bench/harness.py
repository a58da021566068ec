"""One run of one cell: set-up, the measured window, the check, the result.

``run()`` takes the cell as :func:`bench.spec.resolve` returns it and the
devices to use; the entry point (``bench/run.py``) looks for the chip
first. The window drives the system's own entries: the engine from
``repro.sim.make_engine`` through ``engine.run(None, slots, state=...)``
(the engine the configuration's ``engine`` block names, see
:func:`check_engine`),
and, where the mix serves, ``ServeHandle.for_engine`` with
``run(..., snapshot_every=, serve=handle)`` and requests sent by an
open-loop client through ``ServeHandle.predict``.

The check (``correct``) replays every slot the run trained (set-up's
calls and the window's) with the plain reference of
:mod:`bench.reference`, once the window has closed and the program's
state is freed, and compares (under the sharded engine, with the
placement of agents on shards read from the engine before it is freed):

* ``wake_set_diff`` -- agents whose models the program changed against
  those the reference updated: exact, limit 0;
* ``train_gap`` -- the largest gap between the program's models at the
  end of the window and the reference's, over the largest change the
  reference made;
* ``serve_gap`` -- over a sample of the window's requests, drawn from the
  seed and holding the largest, the largest gap between a served score
  and x . theta in f64 of the reference's row at the served version
  (the slot it was published after), over |x| |theta|;
* ``unmatched_versions`` -- sampled requests served from a version that
  is no publication slot of the reference: limit 0.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback

import numpy as np

from bench import deploy, reference, traffic as traffic_mod, workcount
from bench.compile_watch import compile_seconds

TRACE_SECONDS = 2.0  # the traced part of a --trace 1 window
# Percentiles of the request latency over the whole window; the cell's
# end-to-end metrics name the one they report (``predict_p<q>_ms``).
LATENCY_PERCENTILES = (50, 95, 99)


def _stage(name: str, t_start: float) -> None:
    """Log the end of a set-up stage, in seconds since the process began."""
    elapsed = time.perf_counter() - t_start
    print(f"bench: {name} done at {elapsed:.2f} s", file=sys.stderr, flush=True)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Client:
    """Open-loop client: ``senders`` threads take the requests in order of
    their due times, send each when due (or at once when behind), and time
    it from when it was due."""

    def __init__(self, handle, sched, payloads, keep, senders: int):
        self.handle, self.sched, self.payloads, self.keep = handle, sched, payloads, keep
        self.t0 = None
        R = len(sched)
        self.latency = np.full(R, np.nan)
        self.late = np.full(R, np.nan)
        self.failed = 0
        self.errors: list[str] = []
        self.kept: dict[int, tuple] = {}
        self._next = 0
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._send, daemon=True) for _ in range(senders)]

    def start(self, t0: float) -> None:
        self.t0 = t0
        for t in self._threads:
            t.start()

    def join(self) -> None:
        for t in self._threads:
            t.join()

    def _take(self) -> int | None:
        with self._lock:
            i = self._next
            if i >= len(self.sched):
                return None
            self._next += 1
            return i

    def _send(self):
        while (i := self._take()) is not None:
            due = self.t0 + float(self.sched.due_s[i])
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            ids, X = self.payloads[i]
            sent = time.perf_counter()
            try:
                with _annotate("bench.predict"):
                    snap = self.handle.snapshot()
                    res = self.handle.predict(ids, X, at=snap)
                done = time.perf_counter()
            except Exception:  # counted, and as missing every latency limit
                self.latency[i] = np.inf
                with self._lock:
                    self.failed += 1
                    self.errors.append(traceback.format_exc(limit=3))
                continue
            self.latency[i] = done - due
            self.late[i] = sent - due
            if i in self.keep:
                self.kept[i] = (np.asarray(res.values), int(res.version))


ENGINE_KEYS = ("kind", "partition_mode", "relabel", "exchange")
EXCHANGE_KEYS = ("method", "dtype", "error_feedback")


def check_engine(cfg: dict, chips: int) -> dict:
    """The configuration's ``engine`` block (absent: ``{"kind": "async"}``),
    refused where the benchmark cannot build it or the reference cannot
    replay it:

    * ``"async"``: the single-device engine, on one chip;
    * ``"sharded"``: ``ShardedAsyncEngine`` with one shard per chip of the
      cell, on two or more chips; ``partition_mode`` ``"degree"`` or
      ``"contiguous"``; ``relabel`` null or ``"rcm"`` (``"sfc"`` needs
      coordinates the deployment lacks); ``exchange`` with ``method``
      ``"all_gather"``, ``"p2p"`` or ``"auto"`` and ``dtype`` ``"f32"``
      only: a bf16 or int8 halo reads quantised neighbour rows, a
      different result, which the reference does not replay.
    """
    eng = dict(cfg.get("engine") or {"kind": "async"})
    unknown = sorted(set(eng) - set(ENGINE_KEYS))
    if unknown:
        raise ValueError(f"engine: unknown key(s) {unknown}; known {ENGINE_KEYS}")
    kind = eng.get("kind")
    if kind == "async":
        if len(eng) > 1:
            raise ValueError(f"engine: {sorted(set(eng) - {'kind'})} apply to kind 'sharded' only")
        if chips != 1:
            raise ValueError(f"engine 'async' runs on one device; the cell asks for {chips} chips")
        return eng
    if kind != "sharded":
        raise ValueError(f"engine.kind={kind!r}: known 'async', 'sharded'")
    if chips < 2:
        raise ValueError("engine 'sharded' takes one shard per chip; the cell asks for one chip")
    if eng.get("partition_mode", "degree") not in ("degree", "contiguous"):
        raise ValueError(
            f"engine.partition_mode={eng['partition_mode']!r}: known 'degree', 'contiguous'"
        )
    if eng.get("relabel") not in (None, "rcm"):
        raise ValueError(
            f"engine.relabel={eng['relabel']!r}: known null, 'rcm' "
            "(the deployment has no coordinates)"
        )
    ex = dict(eng.get("exchange") or {})
    unknown = sorted(set(ex) - set(EXCHANGE_KEYS))
    if unknown:
        raise ValueError(f"engine.exchange: unknown key(s) {unknown}; known {EXCHANGE_KEYS}")
    if ex.get("method", "auto") not in ("all_gather", "p2p", "auto"):
        raise ValueError(
            f"engine.exchange.method={ex['method']!r}: known 'all_gather', 'p2p', 'auto'"
        )
    if ex.get("dtype", "f32") != "f32":
        raise ValueError(
            f"engine.exchange.dtype={ex['dtype']!r}: only 'f32'; a quantised halo is a "
            "different result, which the reference does not replay"
        )
    if ex.get("error_feedback", False):
        raise ValueError("engine.exchange.error_feedback: the f32 halo has no error to feed back")
    return eng


def engine_config(cfg: dict, mix: dict, n: int, seed: int, devices):
    """``(EngineConfig, shards)`` of the engine the configuration names:
    ``shards`` None for the single-device engine, else one per device."""
    from repro.sim import EngineConfig, ExchangeSpec

    eng = check_engine(cfg, len(devices))
    base = dict(
        slot_wakes=traffic_mod.slot_wakes(mix, n),
        rates=float(mix["clock_rate"]),
        seed=deploy.engine_seed(seed),
        devices=list(devices),
    )
    if eng["kind"] == "async":
        return EngineConfig(**base), None
    placement = dict(
        partition_mode=eng.get("partition_mode", "degree"),
        relabel=eng.get("relabel"),
        exchange=ExchangeSpec(**eng.get("exchange", {})),
    )
    return EngineConfig(**base, **placement), len(devices)


def placement_of(engine):
    """``(placement, facts)`` of a sharded engine, read while it lives:
    the (S, R) agent ids at each shard's rows (n at padding), which the
    reference replays the per-shard clocks on, and the facts the readers
    see. ``(None, None)`` for the single-device engine."""
    part = getattr(engine, "part", None)
    if part is None:
        return None, None
    method = engine.exchange_method
    facts = {
        "shards": int(engine.num_shards),
        "exchange_method": method,
        "rows_per_shard": int(part.rows_per_shard),
        "halo_fraction": float(part.halo_fraction()),
        "exchange_rows_per_slot": int(part.exchange_rows(method)),
    }
    return np.array(part.owned), facts


def build(cfg: dict, mix: dict, dep, seed: int, devices, stage=lambda name: None):
    """The system under test: objective, engine and (where the mix serves)
    the serving handle, made through the program's own entries.
    ``stage(name)`` is called as each part is done."""
    from repro.core import AgentData, make_objective
    from repro.serve import ServeHandle
    from repro.sim import CDUpdate, make_engine

    graph, X = dep.graph(), dep.X()
    stage("graph and padded X on the host")
    obj = make_objective(
        graph,
        AgentData(X=X, y=dep.y, mask=dep.mask),
        cfg["loss"],
        mu=cfg["mu"],
        clip=cfg["clip"],
        mix_mode="sparse",
    )
    del X
    stage("objective")
    ecfg, shards = engine_config(cfg, mix, dep.n, seed, devices)
    engine = make_engine(CDUpdate(obj), ecfg, shards=shards)
    handle = ServeHandle.for_engine(engine) if mix.get("requests") else None
    return obj, engine, handle


def drive(engine, handle, mix: dict, state):
    """One call of the window: ``slots_per_call`` slots through ``engine.run``."""
    every = int(mix["snapshot_every"]) if handle is not None else 0
    return engine.run(
        None, int(mix["slots_per_call"]), state=state, snapshot_every=every, serve=handle
    )


def initial_theta(seed: int, n: int, p: int) -> np.ndarray:
    import jax

    return np.asarray(0.1 * jax.random.normal(deploy.seed_key(seed, 3), (n, p)), np.float32)


def run(cell, seed: int, seconds: float, trace: bool, devices, t_start: float,
        keep_trace: str | None = None) -> dict:
    """Set up, measure ``seconds``, check, and return the result's fields.
    ``keep_trace``: where a traced run also writes its compact events."""
    cfg, mix = cell.cfg, cell.traffic
    traffic_mod.check(mix)
    check_engine(cfg, len(devices))
    kind = devices[0].device_kind

    # -- set-up: deployment, system, warm-up of every shape the run uses
    with compile_seconds() as setup_compiles:
        _stage("start-up", t_start)
        dep = deploy.generate(cfg, seed, devices=devices)
        _stage("generate", t_start)
        obj, engine, handle = build(cfg, mix, dep, seed, devices,
                                    stage=lambda name: _stage(name, t_start))
        _stage("build", t_start)
        theta0 = initial_theta(seed, dep.n, dep.p)
        state0 = engine.init_state(theta0)
        _stage("initial state", t_start)
        res = drive(engine, handle, mix, state0)
        del state0
        _stage("first call", t_start)
        sched = traffic_mod.schedule(mix, dep.counts, dep.test_count, seed, seconds)
        payloads, keep = [], set()
        if sched is not None:
            for u, c in zip(sched.users.tolist(), sched.sizes.tolist()):
                payloads.append((np.full(c, u, np.int64), dep.features(dep.test_items[u, :c])))
            keep = _sample(sched, int(mix["requests"]["check_sample"]), seed)
            for c in sorted(set(sched.sizes.tolist())):
                handle.predict(np.zeros(c, np.int64), np.zeros((c, dep.p), np.float32))
        rates = np.full(dep.n, float(mix["clock_rate"]))
        m = dep.mask.sum(axis=1)
        deg = np.bincount(reference.dep_edges(dep)[0], minlength=dep.n)
        work = workcount.rate_weighted_mean(workcount.per_update(m, deg, dep.p), rates)
        del obj
        gc.collect()
        _stage("warm-up", t_start)
    setup_s = time.perf_counter() - t_start

    # -- the measured window
    client = None
    if sched is not None:
        client = Client(handle, sched, payloads, keep, int(mix["requests"]["senders"]))
    counters0 = handle.counters() if handle is not None else None
    tracer = _Tracer(trace, seconds, keep_trace)
    with compile_seconds() as window_compiles:
        t0 = time.perf_counter()
        applied0, dropped0 = res.wakes_applied, res.wakes_dropped
        if client is not None:
            client.start(t0)
        calls = 0
        while time.perf_counter() - t0 < seconds:
            tracer.before_call(time.perf_counter() - t0, res)
            with _annotate("bench.train_call"):
                res = drive(engine, handle, mix, res.state)
            calls += 1
            tracer.after_call(res)
        t1 = time.perf_counter()
        tracer.close(res)
        if client is not None:
            client.join()
    wall = t1 - t0
    applied = res.wakes_applied - applied0
    dropped = res.wakes_dropped - dropped0
    counters1 = handle.counters() if handle is not None else None
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)

    # -- what the check needs from the program, then free it
    # Slots the run asked for (set-up's call and the window's), not the
    # program's own count.
    theta_prog, trained = np.array(res.Theta), (1 + calls) * int(mix["slots_per_call"])
    placement, placed = placement_of(engine)
    served = []
    if client is not None:
        for i, (values, version) in sorted(client.kept.items()):
            served.append((int(sched.users[i]), payloads[i][1], values, version))
        client.kept.clear()
        client.handle = None
    del engine, handle, res
    gc.collect()

    t_check = time.perf_counter()
    checks = check(cfg, mix, dep, seed, theta0, theta_prog, trained, served,
                   placement=placement, devices=devices)
    check_s = time.perf_counter() - t_check
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if client is not None and client.failed:
        correct = False

    window = {
        "wall_s": wall,
        "applied": applied,
        "dropped": dropped,
        "slots": int(calls * int(mix["slots_per_call"])),
        "calls": calls,
        "compiles": window_compiles["compiles"],
        "setup_compiles": setup_compiles["compiles"],
        "check_s": check_s,
        "placement": placed,
    }
    out = {
        "correct": bool(correct),
        "attempted": int(applied + dropped + (len(sched) if sched is not None else 0)),
        "failed": int(dropped + (client.failed if client is not None else 0)),
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
        "checks": checks,
        "window": window,
        "errors": client.errors[:3] if client is not None else [],
    }
    ctx = {
        "device_kind": kind,
        "p": dep.p,
        "window": window,
        "work": work,
        "client": None
        if client is None
        else {"latency_s": client.latency, "late_s": client.late},
        "serve_counters": None if counters0 is None else (counters0, counters1),
        "trace": tracer.reduced,
        "traced": tracer.window,
        "placement": placed,
    }
    if client is not None:
        for q in LATENCY_PERCENTILES:
            window[f"predict_p{q}_ms"] = float(np.percentile(client.latency * 1e3, q))
    if not trace:
        e2e = {
            "updates_per_s": applied / wall,
            "setup_s": setup_s,
        }
        if client is not None:
            for q in LATENCY_PERCENTILES:
                e2e[f"predict_p{q}_ms"] = window[f"predict_p{q}_ms"]
        out["values"] = e2e
    else:
        out["ctx"] = ctx
        out["device"]["busy_s"] = tracer.reduced["busy_s"]
        out["device"]["window_s"] = tracer.reduced["window_s"]
        out["breakdown"] = tracer.reduced["breakdown"]
    return out


def _sample(sched, k: int, seed: int) -> set:
    """Indices of the requests the check compares: drawn from the seed,
    with the largest request always among them."""
    R = len(sched)
    if R == 0:
        return set()
    rng = np.random.default_rng([int(seed), 13])
    pick = set(rng.choice(R, size=min(k, R), replace=False).tolist())
    pick.add(int(np.argmax(sched.sizes)))
    return pick


def publication_period(mix: dict) -> int:
    """Slots between the versions the reference keeps: the mix's snapshot
    period where it serves, else one call."""
    return int(mix["snapshot_every"]) if mix.get("requests") else int(mix["slots_per_call"])


def sample_users(mix: dict, users) -> np.ndarray:
    """The sampled users, padded with user 0 to one fixed length, so the
    reference compiles one program whatever the sample holds."""
    size = int(mix["requests"]["check_sample"]) + 1 if mix.get("requests") else 1
    out = np.zeros(size, np.int32)
    uniq = np.unique(np.asarray(list(users), np.int64))
    out[: uniq.size] = uniq
    return out


def check(cfg, mix, dep, seed, theta0, theta_prog, trained, served,
          precision: str = "highest", half: bool = False, placement=None,
          devices=None) -> dict:
    """Replay the ``trained`` slots with the reference and compare (see
    the module doc). ``served``: (user, features, scores, version) of the
    sampled requests; ``placement``: the sharded engine's, one shard on
    each of the cell's ``devices``, which the replay then runs on; None
    for the single engine. ``precision``/``half`` only for the control's
    readings."""
    prob = reference.wake_probability(traffic_mod.slot_wakes(mix, dep.n), dep.n)
    users = sample_users(mix, [u for u, *_ in served])
    theta_ref, touched, rows_at = reference.replay(
        dep, cfg, theta0, deploy.engine_seed(seed), prob, trained, publication_period(mix),
        users, precision, half, placement=placement,
        shards=None if devices is None else len(devices), devices=devices,
    )
    pos = {int(u): i for i, u in enumerate(users)}
    pairs = [(X, values, rows_at.get(version), pos[u]) for u, X, values, version in served]
    return numbers(theta0, theta_prog, theta_ref, touched, pairs, cfg["limits"],
                   serving=bool(mix.get("requests")))


def numbers(theta0, theta_prog, theta_ref, touched, pairs, limits, serving: bool) -> dict:
    """The compared numbers of a run, each beside its limit. ``pairs``:
    (features, served scores, the reference's rows at the served version
    or None, the user's position among them) of each sampled request."""
    changed = np.any(theta_prog != theta0, axis=1)
    scale = float(np.max(np.abs(theta_ref - theta0)))
    out = {
        "wake_set_diff": {
            "value": int(np.sum(changed != touched)),
            "limit": 0,
        },
        "train_gap": {
            "value": float(np.max(np.abs(theta_prog - theta_ref)) / max(scale, 1e-30)),
            "limit": limits["train_gap"],
        },
    }
    if serving:
        gap, unmatched = 0.0, 0
        for X, values, rows, i in pairs:
            if rows is None:
                unmatched += 1
                continue
            row = np.asarray(rows[i], np.float64)
            exact = reference.scores(np.broadcast_to(row, X.shape), X, "exact")
            norm = np.linalg.norm(X.astype(np.float64), axis=1) * np.linalg.norm(row)
            gap = max(gap, float(np.max(np.abs(values - exact) / np.maximum(norm, 1e-30))))
        out["serve_gap"] = {"value": gap, "limit": limits["serve_gap"]}
        out["unmatched_versions"] = {"value": unmatched, "limit": 0}
    return out


class _Tracer:
    """Profiles ``TRACE_SECONDS`` of the window's calls in a ``--trace 1``
    run, starting a third of the way in; off otherwise. The traced part
    is the span ``bench.traced_window`` on the driving thread."""

    def __init__(self, on: bool, seconds: float, keep: str | None = None):
        self.on = on
        self.keep = keep
        self.start_at = seconds / 3.0
        self.state = "idle"
        self.dir = None
        self.window = None
        self.reduced = None
        self._span = None
        self._t = None
        self._a0 = None

    def before_call(self, elapsed: float, res):
        if not (self.on and self.state == "idle" and elapsed >= self.start_at):
            return
        import tempfile

        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.traced_window")
        self._span.__enter__()
        self.state = "tracing"
        self._t = time.perf_counter()
        self._a0 = (res.wakes_applied, res.slots)

    def after_call(self, res):
        if self.state == "tracing" and time.perf_counter() - self._t >= TRACE_SECONDS:
            self._stop(res)

    def close(self, res):
        if self.state == "tracing":
            self._stop(res)
        if self.on and self.reduced is None:
            raise RuntimeError("the traced run's window ended before its trace began")

    def _stop(self, res):
        import jax

        from bench import trace_reduce

        self._span.__exit__(None, None, None)
        self.window = {
            "applied": res.wakes_applied - self._a0[0],
            "slots": res.slots - self._a0[1],
            "host_s": time.perf_counter() - self._t,
        }
        jax.profiler.stop_trace()
        self.state = "done"
        self.reduced = trace_reduce.reduce_dir(self.dir, keep=self.keep)
