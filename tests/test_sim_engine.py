"""Batched async engine (repro.sim): cross-validation against the
sequential simulators, DP budget-stop parity, and scenario invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AgentData,
    DPConfig,
    as_csr,
    csr_from_coo,
    knn_graph,
    make_objective,
    ring_graph,
    run,
    run_private,
)
from repro.core.coordinate_descent import _cd_step
from repro.core.model_propagation import propagation_objective
from repro.sim import (
    AsyncEngine,
    CDUpdate,
    ChurnConfig,
    DelayConfig,
    DPCDUpdate,
    PropagationUpdate,
    Scenario,
    StragglerConfig,
)


def _quad_problem(n, p=4, m=3, seed=0, mix_mode="auto", mu=0.5, graph=None):
    rng = np.random.default_rng(seed)
    if graph is None:
        graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "quadratic", mu=mu, mix_mode=mix_mode)


@pytest.fixture(scope="module")
def small_problem():
    return _quad_problem(n=24, seed=1)


# ---------------------------------------------------------------------------
# Determinism and clock statistics
# ---------------------------------------------------------------------------


def test_engine_seeded_determinism(small_problem):
    obj = small_problem
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=6.0, seed=11)
    r1 = eng.run(np.zeros((obj.n, obj.p)), slots=40)
    r2 = eng.run(np.zeros((obj.n, obj.p)), slots=40)
    np.testing.assert_array_equal(r1.Theta, r2.Theta)
    assert r1.messages == r2.messages and r1.wakes_applied == r2.wakes_applied

    r3 = AsyncEngine(CDUpdate(obj), slot_wakes=6.0, seed=12).run(
        np.zeros((obj.n, obj.p)), slots=40
    )
    assert not np.array_equal(r1.Theta, r3.Theta)


def test_thinned_wake_rate_matches_expectation(small_problem):
    obj = small_problem
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=6.0, seed=0)
    slots = 200
    res = eng.run(np.zeros((obj.n, obj.p)), slots=slots)
    mu = sum(eng.wake_probs) * slots
    sigma = np.sqrt(mu)
    assert abs(res.wakes_applied - mu) < 6 * sigma
    assert res.wakes_dropped == 0  # B = mean + 6 sigma: overflow ~impossible


def test_heterogeneous_rates_skew_wake_counts(small_problem):
    obj = small_problem
    n = obj.n
    rates = np.where(np.arange(n) < n // 2, 8.0, 0.5)
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=6.0, rates=rates, seed=3)
    state = eng.init_state(np.zeros((n, obj.p)))
    woke = np.zeros(n)
    for _ in range(60):
        prev = np.asarray(state.Theta)
        state = eng.advance(state, 1)
        woke += np.any(np.asarray(state.Theta) != prev, axis=1)
    # Fast agents (16x rate) must wake far more often than slow ones.
    assert woke[: n // 2].mean() > 3.0 * max(woke[n // 2 :].mean(), 1e-9)


def test_slot_capacity_overflow_is_counted(small_problem):
    obj = small_problem
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=4.0, batch_size=2, seed=0)
    state = eng.init_state(np.zeros((obj.n, obj.p)))
    mask = np.zeros(obj.n, dtype=bool)
    mask[:5] = True
    state = eng.step(state, mask)
    assert int(state.applied) == 2 and int(state.dropped) == 3


# ---------------------------------------------------------------------------
# Cross-validation against the sequential simulators
# ---------------------------------------------------------------------------


def test_forced_single_wakes_match_sequential_run_exactly(small_problem):
    """One agent per slot, no scenario: the engine IS the faithful simulator."""
    obj = small_problem
    rng = np.random.default_rng(5)
    wake_seq = rng.integers(0, obj.n, size=30)
    r_seq = run(obj, np.zeros((obj.n, obj.p)), T=30, rng=rng, wake_sequence=wake_seq)

    eng = AsyncEngine(CDUpdate(obj), slot_wakes=1.0, seed=0)
    state = eng.init_state(np.zeros((obj.n, obj.p)))
    for i in wake_seq:
        mask = np.zeros(obj.n, dtype=bool)
        mask[i] = True
        state = eng.step(state, mask)
    np.testing.assert_allclose(np.asarray(state.Theta), r_seq.Theta, rtol=1e-5, atol=1e-6)
    assert float(state.messages) == r_seq.messages[-1]


def test_batched_slot_equals_snapshot_updates(small_problem):
    """A multi-agent slot applies each woken agent's update from the same
    start-of-slot snapshot (bounded staleness, the recorded deviation)."""
    obj = small_problem
    rng = np.random.default_rng(6)
    Theta0 = rng.normal(size=(obj.n, obj.p))
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=4.0, seed=0, dtype=jnp.float64)
    state = eng.init_state(Theta0)
    woken = [0, 3, 9, 17]
    mask = np.zeros(obj.n, dtype=bool)
    mask[woken] = True
    state = eng.step(state, mask)

    snap = jnp.asarray(Theta0, jnp.float64)
    expected = np.array(snap)
    for i in woken:
        expected[i] = np.asarray(_cd_step(obj, snap, i))[i]
    np.testing.assert_allclose(np.asarray(state.Theta), expected, rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("mix_mode", ["dense", "sparse"])
def test_engine_reaches_sequential_fixed_point_512(mix_mode):
    """Acceptance: batched engine matches the sequential CD fixed point
    within 1e-5 at n=512, dense and sparse backends."""
    obj = _quad_problem(n=512, seed=0, mix_mode=mix_mode)
    Theta_star = obj.solve_exact()
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=128.0, seed=3, dtype=jnp.float64)
    res = eng.run(np.zeros((obj.n, obj.p)), slots=700)
    assert np.abs(res.Theta - Theta_star).max() < 1e-5
    # And the sequential optimum is an engine fixed point.
    state = eng.init_state(Theta_star)
    state = eng.advance(state, 5)
    assert np.abs(np.asarray(state.Theta) - Theta_star).max() < 1e-9


def test_dense_and_sparse_backends_agree_trajectorywise():
    dense = _quad_problem(n=48, seed=2, mix_mode="dense")
    sparse = _quad_problem(n=48, seed=2, mix_mode="sparse")
    rd = AsyncEngine(CDUpdate(dense), slot_wakes=8.0, seed=4, dtype=jnp.float64).run(
        np.zeros((48, 4)), slots=60
    )
    rs = AsyncEngine(CDUpdate(sparse), slot_wakes=8.0, seed=4, dtype=jnp.float64).run(
        np.zeros((48, 4)), slots=60
    )
    np.testing.assert_allclose(rd.Theta, rs.Theta, rtol=1e-9, atol=1e-11)


# ---------------------------------------------------------------------------
# DP-CD: budget stopping parity with dp_cd.run_private
# ---------------------------------------------------------------------------


def _logistic_problem(n=8, p=3, m=4, seed=0):
    rng = np.random.default_rng(seed)
    from repro.core import erdos_renyi_graph

    graph = erdos_renyi_graph(n, 0.5, rng)
    targets = rng.normal(size=(n, p))
    X = rng.normal(size=(n, m, p))
    y = np.sign(np.einsum("nmp,np->nm", X, targets))
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "logistic", mu=0.3)


def test_dp_budget_stop_parity_with_dp_cd():
    obj = _logistic_problem()
    n = obj.n
    planned_Ti = 3
    cfg = DPConfig(eps_bar=0.8)

    # Sequential driver: round-robin wakes sized so run_private plans
    # T // n == planned_Ti and every agent wakes at least that often —
    # everyone spends exactly planned_Ti equal steps, then stops.
    wake = np.concatenate([np.tile(np.arange(n), planned_Ti), np.arange(n - 1)])
    seq = run_private(
        obj, np.zeros((n, obj.p)), T=len(wake), cfg=cfg,
        rng=np.random.default_rng(0), wake_sequence=wake, record_objective=False,
    )

    # Engine: forced all-wake slots until everyone exceeds the plan.
    upd = DPCDUpdate.plan(obj, cfg, planned_Ti=planned_Ti)
    assert upd.planned_Ti == len(wake) // n  # same plan as run_private's T//n
    eng = AsyncEngine(upd, slot_wakes=float(n), seed=0)
    state = eng.init_state(np.zeros((n, obj.p)))
    for _ in range(5):
        state = eng.step(state, np.ones(n, dtype=bool))

    counts = np.asarray(state.ustate)
    np.testing.assert_array_equal(counts, np.full(n, planned_Ti))
    eps_engine = upd.eps_spent(state.ustate)
    np.testing.assert_allclose(eps_engine, seq.eps_spent, rtol=1e-10)
    assert np.all(eps_engine <= cfg.eps_bar + 1e-9)


def test_dp_exhausted_agents_freeze():
    obj = _logistic_problem(seed=1)
    n = obj.n
    upd = DPCDUpdate.plan(obj, DPConfig(eps_bar=0.5), planned_Ti=2)
    eng = AsyncEngine(upd, slot_wakes=float(n), seed=0)
    state = eng.init_state(np.zeros((n, obj.p)))
    for _ in range(2):
        state = eng.step(state, np.ones(n, dtype=bool))
    frozen = np.asarray(state.Theta)
    msgs = float(state.messages)
    state = eng.step(state, np.ones(n, dtype=bool))  # budget spent: no-ops
    np.testing.assert_array_equal(np.asarray(state.Theta), frozen)
    assert float(state.messages) == msgs  # nothing broadcast either
    assert int(state.applied) == 2 * n


def test_dp_plan_rejects_prop2_schedule():
    obj = _logistic_problem(seed=2)
    with pytest.raises(NotImplementedError):
        DPCDUpdate.plan(obj, DPConfig(eps_bar=0.5, schedule="prop2"), planned_Ti=3)


def test_compose_uniform_vectorizes_over_agents():
    """The vectorized accounting behind DPCDUpdate.eps_spent == per-agent
    compose_kairouz. (Lives here, not test_privacy.py, which is
    hypothesis-gated and skips entirely on containers without it.)"""
    from repro.core.privacy import compose_kairouz, compose_uniform

    counts = np.array([0, 1, 5, 40])
    got = compose_uniform(0.2, counts, 1e-5)
    want = [compose_kairouz(np.full(k, 0.2), 1e-5) for k in counts]
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert got[0] == 0.0


# ---------------------------------------------------------------------------
# Scenarios: churn, delay, stragglers
# ---------------------------------------------------------------------------


def test_churn_departed_agents_params_frozen(small_problem):
    obj = small_problem
    n = obj.n
    leavers = np.zeros(n)
    leavers[[2, 5, 11]] = 1.0  # depart deterministically at slot 0
    sc = Scenario(churn=ChurnConfig(leave_prob=leavers, rejoin_prob=0.0))
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=1, scenario=sc)
    rng = np.random.default_rng(0)
    Theta0 = rng.normal(size=(n, obj.p)).astype(np.float32)  # engine dtype: exact freeze
    res = eng.run(Theta0, slots=80)
    np.testing.assert_array_equal(res.Theta[[2, 5, 11]], Theta0[[2, 5, 11]])
    assert not res.active[[2, 5, 11]].any()
    # The rest of the network kept training (and mixed the frozen models).
    others = np.setdiff1d(np.arange(n), [2, 5, 11])
    assert np.abs(res.Theta[others] - Theta0[others]).max() > 1e-3


def test_straggler_drop_prob_one_loses_everything(small_problem):
    obj = small_problem
    sc = Scenario(straggler=StragglerConfig(drop_prob=1.0))
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=1, scenario=sc)
    Theta0 = np.random.default_rng(0).normal(size=(obj.n, obj.p)).astype(np.float32)
    res = eng.run(Theta0, slots=30)
    np.testing.assert_array_equal(res.Theta, Theta0)
    assert res.wakes_applied == 0 and res.messages == 0.0


def test_delayed_messages_lag_and_arrive_in_order():
    """Per-edge delay d: a woken agent mixes neighbour state from d slots
    ago, and successive broadcasts arrive in send order (FIFO)."""
    n, p = 3, 2
    graph = ring_graph(n)
    obj = _quad_problem(n=n, p=p, m=2, seed=3, graph=graph, mix_mode="dense")
    d = 2
    sc = Scenario(delay=DelayConfig(max_delay=d, edge_delays=d))
    eng = AsyncEngine(
        CDUpdate(obj), slot_wakes=1.0, seed=0, scenario=sc, dtype=jnp.float64
    )
    rng = np.random.default_rng(4)
    Theta0 = rng.normal(size=(n, p))
    state = eng.init_state(Theta0)

    def wake(state, i):
        mask = np.zeros(n, dtype=bool)
        mask[i] = True
        return eng.step(state, mask)

    snapshots = [np.asarray(state.Theta)]  # start-of-slot states
    state = wake(state, 0)  # slot 0: theta_0 -> v1
    snapshots.append(np.asarray(state.Theta))
    state = wake(state, 0)  # slot 1: theta_0 -> v2
    snapshots.append(np.asarray(state.Theta))

    def expected_row1(state, lagged):
        """Eq. 4 for agent 1 where neighbours are read from ``lagged``."""
        view = lagged.copy()
        view[1] = np.asarray(state.Theta)[1]  # own block is always current
        return np.asarray(_cd_step(obj, jnp.asarray(view), 1))[1]

    # Slot 2: agent 1 must see theta_0 as of slot 2 - d = 0 (the initial
    # value), not v1 or v2.
    exp = expected_row1(state, snapshots[0])
    state = wake(state, 1)
    np.testing.assert_allclose(np.asarray(state.Theta)[1], exp, rtol=1e-12)

    # Slot 3: now the slot-1 snapshot (v1) arrives — the earlier broadcast
    # lands first; delayed messages are applied in send order.
    exp = expected_row1(state, snapshots[1])
    state = wake(state, 1)
    np.testing.assert_allclose(np.asarray(state.Theta)[1], exp, rtol=1e-12)


def test_zero_delay_config_matches_no_delay_engine(small_problem):
    obj = small_problem
    sc = Scenario(delay=DelayConfig(max_delay=0, edge_delays=0))
    r_delay = AsyncEngine(
        CDUpdate(obj), slot_wakes=8.0, seed=9, scenario=sc, dtype=jnp.float64
    ).run(np.zeros((obj.n, obj.p)), slots=40)
    r_plain = AsyncEngine(
        CDUpdate(obj), slot_wakes=8.0, seed=9, dtype=jnp.float64
    ).run(np.zeros((obj.n, obj.p)), slots=40)
    np.testing.assert_allclose(r_delay.Theta, r_plain.Theta, rtol=1e-9, atol=1e-11)


def test_full_scenario_still_converges(small_problem):
    """Churn + delay + stragglers: objective still heads downhill."""
    obj = small_problem
    sc = Scenario(
        churn=ChurnConfig(leave_prob=0.02, rejoin_prob=0.3),
        delay=DelayConfig(max_delay=2, edge_delays=1),
        straggler=StragglerConfig(drop_prob=0.2),
    )
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=5, scenario=sc)
    res = eng.run(np.zeros((obj.n, obj.p)), slots=150, record_every=150)
    assert res.objective[-1] < 0.75 * res.objective[0]
    assert np.isfinite(res.Theta).all()


# ---------------------------------------------------------------------------
# record_every validation
# ---------------------------------------------------------------------------


class _NoObjectiveUpdate:
    """A LocalUpdate without an objective method (delegates the rest)."""

    def __init__(self, inner):
        self._inner = inner
        self.n, self.p, self.graph, self.mix = inner.n, inner.p, inner.graph, inner.mix

    def init_state(self):
        return self._inner.init_state()

    def apply(self, *args, **kw):
        return self._inner.apply(*args, **kw)

    def apply_rows(self, *args, **kw):
        return self._inner.apply_rows(*args, **kw)


def test_record_every_without_objective_raises(small_problem):
    """Asking for an objective trace the update cannot produce must be a
    loud error, not a silently-ignored record_every."""
    obj = small_problem
    upd = _NoObjectiveUpdate(CDUpdate(obj))
    eng = AsyncEngine(upd, slot_wakes=4.0, seed=0)
    with pytest.raises(ValueError, match="record_every"):
        eng.run(np.zeros((obj.n, obj.p)), slots=4, record_every=2)
    # record_every=0 still runs fine without an objective.
    res = eng.run(np.zeros((obj.n, obj.p)), slots=4)
    assert res.objective is None and res.slots == 4


# ---------------------------------------------------------------------------
# Model propagation through the same engine
# ---------------------------------------------------------------------------


def test_propagation_update_converges_to_exact_solution():
    rng = np.random.default_rng(0)
    n, p = 20, 3
    graph = knn_graph(rng.normal(size=(n, 6)), k=5)
    theta_loc = rng.normal(size=(n, p))
    conf = np.ones(n)
    upd = PropagationUpdate(graph=graph, theta_loc=theta_loc, mu=0.5, confidences=conf)
    eng = AsyncEngine(upd, slot_wakes=5.0, seed=2, dtype=jnp.float64)
    res = eng.run(theta_loc, slots=400, record_every=200)
    _, solve = propagation_objective(graph, theta_loc, 0.5, conf)
    star = solve()
    assert np.abs(res.Theta - star).max() < 1e-6
    assert res.objective[-1] <= res.objective[0]


# ---------------------------------------------------------------------------
# TPU compile options of the super-tick scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kind,rows,p,guarded",
    [
        ("TPU v5 lite", 1_000_000, 20, True),  # 96 MB slab: the shape that halted
        ("TPU v5 lite", 700_000, 20, True),  # just past half the 128 MiB VMEM
        ("TPU v5 lite", 600_000, 20, False),  # ran unguarded on the chip
        ("TPU v5 lite", 1_000_000, 8, False),
        ("TPU v7 unknown", 1_000_000, 20, False),  # no guard for this kind
    ],
)
def test_scan_compiler_options_follow_slab_size(monkeypatch, kind, rows, p, guarded):
    """The VMEM guard engages on a TPU from the model slab's compact size
    alone: at half the chip's VMEM and up, and only on a kind it knows."""
    from repro.sim import engine as engine_mod

    class _Dev:
        device_kind = kind

    monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(engine_mod.jax, "devices", lambda *a: [_Dev()])
    opts = engine_mod._scan_compiler_options(rows, p, jnp.float32)
    want = engine_mod._VMEM_GUARD["TPU v5 lite"][0] if guarded else None
    assert opts == want


def test_scan_compiler_options_off_tpu():
    from repro.sim.engine import _scan_compiler_options

    assert _scan_compiler_options(10**7, 20, jnp.float32) is None


# ---------------------------------------------------------------------------
# Static tables placed in the scan chunk's layouts
# ---------------------------------------------------------------------------


def _tiled_agent_major(engine, state, static):
    """Formats such as a TPU compiler asks for: every table of rank 2 or
    more agent-major with its other axes reversed, in (8, 128) tiles. On
    the CPU, whose compiler asks for the default layouts, they drive the
    packing path."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    cpu = SingleDeviceSharding(jax.devices()[0])

    def fmt(x):
        if x.ndim < 2:
            return Format(Layout((0,)), cpu)
        order = (0,) + tuple(range(x.ndim - 1, 0, -1))
        return Format(Layout(order, tiling=((8, 128),)), cpu)

    return jax.tree.map(fmt, static)


@pytest.mark.parametrize(
    "formats", ["compiler", "tiled_agent_major", "tiled_agent_major_delay"]
)
def test_placed_static_tables_run_bit_identical(monkeypatch, formats):
    """An engine whose tables were stored for the formats asked for runs
    ``step``, ``advance(16)``, ``advance(5)``, ``step`` to the same bits as
    one whose tables went through plain ``jnp.asarray``; every stored
    table reads back by rows equal to the host arrays, and the gauges
    count the tables stored off the default: none where the compiler
    asks for the default layouts (the CPU), all of rank 2 or more,
    permuted and padded to the tile, where it asks for tiled agent-major
    layouts: X, y and mask (the CSR neighbour tables have one axis), and
    under a delay scenario also its (n, K) int32 neighbour ids, weights
    and delays."""
    from repro.sim.engine import _RowTable

    obj = _quad_problem(n=24, seed=1, mix_mode="sparse")
    kw = dict(slot_wakes=6.0, seed=5)
    delay = formats.endswith("_delay")
    if delay:
        kw["scenario"] = Scenario(delay=DelayConfig(max_delay=2, edge_delays=1))
    plain = AsyncEngine(CDUpdate(obj), **kw)
    plain._static = jax.tree.map(jnp.asarray, plain._static_tables())
    if formats.startswith("tiled_agent_major"):
        monkeypatch.setattr(AsyncEngine, "static_formats", _tiled_agent_major)
    eng = AsyncEngine(CDUpdate(obj), **kw)

    def is_table(t):
        return isinstance(t, _RowTable)

    packed = [t for t in jax.tree.leaves(eng._static, is_leaf=is_table) if is_table(t)]
    meta = eng.report_meta()
    if formats == "compiler":
        assert not packed
        assert (meta["static_tables_relaid"], meta["static_relaid_bytes"]) == (0, 0)
    else:
        assert len(packed) == meta["static_tables_relaid"] == (6 if delay else 3)
        if delay:
            idx = eng._static["idx"]
            assert is_table(idx) and idx.data.dtype == np.int32
        assert meta["static_relaid_bytes"] == sum(t.data.nbytes for t in packed)
        X = eng._static["consts"]["X"]
        assert X.perm == (0, 2, 1) and X.data.shape == (obj.n, 8, 128)

    rows = np.arange(obj.n)
    jax.tree.map(
        lambda t, ref: np.testing.assert_array_equal(t[rows] if is_table(t) else t, ref),
        eng._static, plain._static, is_leaf=is_table,
    )
    X = eng._static["consts"]["X"][rows]
    np.testing.assert_array_equal(X, np.asarray(obj.data.X, np.float32))

    rng = np.random.default_rng(2)
    masks = rng.random((2, obj.n)) < 0.3
    theta0 = rng.normal(size=(obj.n, obj.p))

    def drive(e):
        s = e.step(e.init_state(theta0), masks[0])
        s = e.advance(s, 16)
        s = e.advance(s, 5)
        return e.step(s, masks[1])

    out, ref = drive(eng), drive(plain)
    assert int(out.applied) > 0
    jax.tree.map(np.testing.assert_array_equal, out, ref)


# ---------------------------------------------------------------------------
# The woken rows' CSR neighbour sums on a hub graph
# ---------------------------------------------------------------------------


def _hub_problem(n=300, hubs=6, hub_degree=200, seed=21):
    """A quadratic problem over a 4-NN graph joined to ``hubs`` stars of
    ``hub_degree`` spokes: a few rows far wider than the rest, so the
    padded (B, K) block would be mostly padding."""
    rng = np.random.default_rng(seed)
    knn = as_csr(knn_graph(rng.normal(size=(n, 8)), k=4))
    spokes = [rng.choice(np.arange(hubs, n), hub_degree, replace=False) for _ in range(hubs)]
    rows = np.concatenate([knn.row_ids(), np.repeat(np.arange(hubs), hub_degree)])
    cols = np.concatenate([knn.indices, *spokes])
    graph = csr_from_coo(n, rows, cols, np.ones(len(rows)), symmetrize=True)
    return _quad_problem(n, graph=graph, mix_mode="sparse", seed=seed)


def _np_eq4(obj, Theta, woken):
    """The woken agents' Eq. 4 step from the snapshot ``Theta``, in numpy
    (quadratic loss, no clip): each row's neighbour sum over its CSR edges."""
    csr = as_csr(obj.graph)
    X, y, mask = (np.asarray(a, np.float64) for a in (obj.data.X, obj.data.y, obj.data.mask))
    d, c, alpha, lam = obj.degrees, obj.confidences, obj.alphas(), obj.lambdas
    Theta = np.asarray(Theta, np.float64)
    out = Theta.copy()
    for i in woken:
        lo, hi = csr.indptr[i], csr.indptr[i + 1]
        neigh = csr.data[lo:hi] @ Theta[csr.indices[lo:hi]] / d[i]
        resid = X[i] @ Theta[i] - y[i]
        grad = (2.0 * resid * mask[i]) @ X[i] / max(mask[i].sum(), 1.0) + 2.0 * lam[i] * Theta[i]
        out[i] = (1.0 - alpha[i]) * Theta[i] + alpha[i] * (neigh - obj.mu * c[i] * grad)
    return out


def _woken(eng, state, mask=None):
    """The rows the engine's next slot updates: a forced ``mask``'s first B
    agents, or the sampled slot's, drawn by the engine's own rule."""
    if mask is None:
        k_wake = jax.random.split(state.key, 6)[3]
        mask = np.asarray(jax.random.uniform(k_wake, (eng.n,)) < eng._static["wake_probs"])
    return np.nonzero(mask)[0][: eng.batch_size]


def _hub_masks(n, rng):
    """Forced wake sets: every hub with 20 others (more edges than one
    block holds), then a plain draw of 20."""
    hubs = np.zeros(n, bool)
    hubs[:6] = True
    hubs[rng.choice(np.arange(6, n), 20, replace=False)] = True
    plain = np.zeros(n, bool)
    plain[rng.choice(n, 20, replace=False)] = True
    return [hubs, plain]


def test_hub_graph_slots_match_numpy_eq4():
    """On a hub graph, forced and sampled slots of the engine give the
    numpy Eq. 4 step of the rows they wake, to 1e-6."""
    obj = _hub_problem()
    eng = AsyncEngine(CDUpdate(obj), slot_wakes=10.0, seed=3)
    assert eng.mix_edge_block is not None
    rng = np.random.default_rng(4)
    state = eng.init_state(rng.normal(size=(obj.n, obj.p)))
    for mask in _hub_masks(obj.n, rng) + [None] * 3:
        want = _np_eq4(obj, state.Theta, _woken(eng, state, mask))
        state = eng.advance(state, 1) if mask is None else eng.step(state, mask)
        np.testing.assert_allclose(np.asarray(state.Theta), want, rtol=1e-6, atol=1e-6)


def test_hub_graph_mix_counters_and_bit_exact_theta():
    """With metrics on, ``mix_edges`` is the woken valid rows' degrees
    summed and ``mix_trips`` the sum over slots of ceil(edges / E), with E
    the engine's ``mix_edge_block``; Theta is bit-exact with metrics off."""
    obj = _hub_problem()
    kw = dict(slot_wakes=10.0, seed=3)
    eng_off = AsyncEngine(CDUpdate(obj), **kw)
    eng_on = AsyncEngine(CDUpdate(obj), metrics=True, **kw)
    E = eng_on.report_meta()["mix_edge_block"]
    assert E == eng_on.mix_edge_block == eng_off.mix_edge_block
    deg = np.diff(as_csr(obj.graph).indptr)
    rng = np.random.default_rng(5)
    theta0 = rng.normal(size=(obj.n, obj.p))
    s_off, s_on = eng_off.init_state(theta0), eng_on.init_state(theta0)
    per_slot = []
    for mask in _hub_masks(obj.n, rng) + [None] * 4:
        per_slot.append(int(deg[_woken(eng_on, s_on, mask)].sum()))
        if mask is None:
            s_off, s_on = eng_off.advance(s_off, 1), eng_on.advance(s_on, 1)
        else:
            s_off, s_on = eng_off.step(s_off, mask), eng_on.step(s_on, mask)
    np.testing.assert_array_equal(np.asarray(s_off.Theta), np.asarray(s_on.Theta))
    counters, _ = eng_on.metrics_snapshot(s_on)
    assert int(counters["mix_edges"]) == sum(per_slot)
    trips = [-(-e // E) for e in per_slot]
    assert int(counters["mix_trips"]) == sum(trips) and max(trips) >= 2
