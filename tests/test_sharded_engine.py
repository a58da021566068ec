"""Multi-device sharded async engine: cross-validation against the
single-device engine.

In-process tests run on the 1 visible CPU device (a 1-shard mesh is a
legal degenerate case and must already match the single-device engine
bit-for-bit under forced wakes). Multi-device semantics — forced-wake
exact parity, 512-agent fixed-point agreement across 2/4/8 shards, and
DP budget-stop parity — run in a subprocess with 8 XLA host devices, in
the ``test_spmd.py`` style, so this process keeps seeing 1 device."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AgentData, knn_graph, make_objective
from repro.sim import (
    AsyncEngine,
    CDUpdate,
    DelayConfig,
    Scenario,
    ShardedAsyncEngine,
)


def _quad_problem(n, p=4, m=3, seed=0, mu=0.5):
    rng = np.random.default_rng(seed)
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "quadratic", mu=mu, mix_mode="sparse")


def test_single_shard_forced_wakes_match_single_device_bitwise():
    """S=1 is the degenerate mesh: same tiles, empty halo — the sharded
    super-tick must reproduce AsyncEngine exactly under forced wakes."""
    obj = _quad_problem(n=40, seed=1)
    n, p = obj.n, obj.p
    eng1 = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0, dtype=jnp.float64)
    engS = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, slot_wakes=8.0, seed=0, dtype=jnp.float64
    )
    s1 = eng1.init_state(np.zeros((n, p)))
    sS = engS.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(7)
    for _ in range(10):
        mask = rng.random(n) < 0.25
        s1 = eng1.step(s1, mask)
        sS = engS.step(sS, mask)
    np.testing.assert_array_equal(np.asarray(s1.Theta), engS.global_theta(sS))
    assert float(s1.messages) == float(np.asarray(sS.messages).sum())
    assert int(s1.applied) == int(np.asarray(sS.applied).sum())


def test_sharded_sampled_run_reaches_fixed_point_single_shard():
    obj = _quad_problem(n=96, seed=2)
    star = obj.solve_exact()
    eng = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, slot_wakes=24.0, seed=3, dtype=jnp.float64
    )
    res = eng.run(np.zeros((obj.n, obj.p)), slots=500, record_every=250)
    assert np.abs(res.Theta - star).max() < 1e-5
    assert res.objective[-1] <= res.objective[0]
    assert res.slots == 500


def test_relabeled_forced_wakes_match_single_device_bitwise():
    """The permutation round-trip at the engine level: relabel -> run ->
    results come back in original ids and equal the unrelabeled run (which
    itself equals AsyncEngine bit-for-bit). S=1 exercises the full relabel
    machinery in-process; multi-shard relabeling runs in the 8-device
    subprocess scripts below."""
    obj = _quad_problem(n=40, seed=1)
    n, p = obj.n, obj.p
    eng1 = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0, dtype=jnp.float64)
    s1 = eng1.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(7)
    masks = [rng.random(n) < 0.25 for _ in range(6)]
    for mask in masks:
        s1 = eng1.step(s1, mask)
    ref = np.asarray(s1.Theta)
    shuffle = np.random.default_rng(8).permutation(n)
    for relabel in ("rcm", shuffle):
        engS = ShardedAsyncEngine(
            CDUpdate(obj), num_shards=1, relabel=relabel,
            slot_wakes=8.0, seed=0, dtype=jnp.float64,
        )
        assert not np.array_equal(engS.part.order, np.arange(n)) or relabel == "rcm"
        sS = engS.init_state(np.zeros((n, p)))
        for mask in masks:
            sS = engS.step(sS, mask)
        np.testing.assert_array_equal(engS.global_theta(sS), ref)


def test_sharded_super_tick_closes_over_no_per_agent_array():
    """Acceptance: obj.data (and every per-agent constant) is
    shard-resident — the jitted sharded super-tick must not close over
    any array with n or more elements; everything that scales with n
    arrives as a shard_map input sliced along the shards axis."""
    obj = _quad_problem(n=48, seed=5)
    n = obj.n
    eng = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, seed=0)
    state = eng.init_state(np.zeros((n, obj.p)))
    mask = jnp.asarray(eng.part.pad_rows(np.ones(n, bool), fill=False))
    jaxpr = jax.make_jaxpr(eng._forced_impl)(state, eng._static, mask)
    leaked = [
        np.shape(c) for c in jaxpr.consts if hasattr(c, "shape") and np.size(c) >= n
    ]
    assert not leaked, f"replicated per-agent constants leaked into the super-tick: {leaked}"
    # The single-device engine passes its O(n) tables as arguments too.
    eng1 = AsyncEngine(CDUpdate(obj), seed=0)
    s1 = eng1.init_state(np.zeros((n, obj.p)))
    jaxpr1 = jax.make_jaxpr(eng1._slot_forced)(s1, eng1._static, jnp.ones(n, bool))
    leaked1 = [
        np.shape(c) for c in jaxpr1.consts if hasattr(c, "shape") and np.size(c) >= n
    ]
    assert not leaked1, f"per-agent constants leaked into the single-device slot: {leaked1}"
    # Sanity-check the check: a row step that gathers from the replicated
    # arrays does close over them, so the probe can tell the difference.
    from repro.core.coordinate_descent import eq4_rows

    rows = jnp.arange(4)
    jaxpr_rep = jax.make_jaxpr(lambda th: eq4_rows(obj, th, rows, th[rows]))(s1.Theta)
    assert any(hasattr(c, "shape") and np.size(c) >= n for c in jaxpr_rep.consts)


def test_default_batch_size_follows_owned_agents_under_relabel():
    """Regression: B_s must be sized from each shard's *owned agents'*
    rates (bounds index positions, not ids, under a relabel), so every
    shard's expected wake mass stays covered to mean + 6 sigma."""
    from repro.sim import clocks

    obj = _quad_problem(n=60, seed=6)
    rates = np.where(np.arange(obj.n) % 3 == 0, 25.0, 0.04)  # skewed classes
    eng = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, relabel="rcm", rates=rates, slot_wakes=16.0
    )
    part = eng.part
    for s in range(part.num_shards):
        owned = part.owned[s, : int(part.sizes[s])]
        need = clocks.default_batch_size(rates[owned], eng.tau)
        assert eng.batch_size >= min(need, part.rows_per_shard), (s, need)


def test_sharded_engine_rejects_delay_and_bad_shard_counts():
    obj = _quad_problem(n=24, seed=3)
    with pytest.raises(NotImplementedError, match="delay"):
        ShardedAsyncEngine(
            CDUpdate(obj), num_shards=1,
            scenario=Scenario(delay=DelayConfig(max_delay=1)),
        )
    with pytest.raises(ValueError, match="devices"):
        ShardedAsyncEngine(CDUpdate(obj), num_shards=9999)


class _NoObjectiveUpdate:
    def __init__(self, inner):
        self._inner = inner
        self.n, self.p, self.graph, self.mix = inner.n, inner.p, inner.graph, inner.mix

    def init_state(self):
        return self._inner.init_state()

    def apply(self, *args, **kw):
        return self._inner.apply(*args, **kw)

    def apply_rows(self, *args, **kw):
        return self._inner.apply_rows(*args, **kw)


def test_sharded_record_every_without_objective_raises():
    obj = _quad_problem(n=24, seed=4)
    eng = ShardedAsyncEngine(_NoObjectiveUpdate(CDUpdate(obj)), num_shards=1, seed=0)
    with pytest.raises(ValueError, match="record_every"):
        eng.run(np.zeros((obj.n, obj.p)), slots=2, record_every=1)


MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import (AgentData, DPConfig, erdos_renyi_graph, knn_graph,
                            make_objective, run_private)
    from repro.sim import (AsyncEngine, CDUpdate, DPCDUpdate, ExchangeSpec,
                       ShardedAsyncEngine)

    assert len(jax.devices()) == 8

    def quad(n, p=4, m=3, seed=0):
        rng = np.random.default_rng(seed)
        graph = knn_graph(rng.normal(size=(n, 8)), k=8)
        targets = rng.normal(size=(n, p)) / np.sqrt(p)
        X = rng.normal(size=(n, m, p)) / np.sqrt(p)
        y = np.einsum("nmp,np->nm", X, targets)
        data = AgentData(X=X, y=y, mask=np.ones((n, m)))
        return make_objective(graph, data, "quadratic", mu=0.5, mix_mode="sparse")

    # 1) Forced wake sets: bit-exact parity with the single-device engine
    #    across partition modes, relabel passes, and both halo-exchange
    #    wire formats, including counters.
    obj = quad(64, seed=1)
    n, p = obj.n, obj.p
    eng1 = AsyncEngine(CDUpdate(obj), slot_wakes=8.0, seed=0, dtype=jnp.float64)
    s1 = eng1.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(5)
    masks = [rng.random(n) < 0.3 for _ in range(12)]
    for mask in masks:
        s1 = eng1.step(s1, mask)
    configs = [
        dict(partition_mode="contiguous"),
        dict(partition_mode="degree"),
        dict(partition_mode="degree", exchange=ExchangeSpec(method="p2p")),
        dict(partition_mode="degree", relabel="rcm",
             exchange=ExchangeSpec(method="all_gather")),
        dict(partition_mode="degree", relabel="rcm", exchange=ExchangeSpec(method="p2p")),
        dict(partition_mode="contiguous", relabel="rcm", exchange=ExchangeSpec()),
    ]
    for kw in configs:
        engS = ShardedAsyncEngine(CDUpdate(obj), num_shards=4, slot_wakes=8.0,
                                  seed=0, dtype=jnp.float64, **kw)
        sS = engS.init_state(np.zeros((n, p)))
        for mask in masks:
            sS = engS.step(sS, mask)
        assert np.array_equal(np.asarray(s1.Theta), engS.global_theta(sS)), kw
        assert float(s1.messages) == float(np.asarray(sS.messages).sum())
        assert int(s1.applied) == int(np.asarray(sS.applied).sum())
    print("FORCED_PARITY_OK")

    # 2) DP budget-stop parity under sharding (with the locality relabel
    #    and point-to-point exchange engaged): forced all-wake slots spend
    #    exactly the planned budget, matching run_private and the
    #    single-device engine's accounting.
    rngd = np.random.default_rng(0)
    gd = erdos_renyi_graph(12, 0.5, rngd)
    td = rngd.normal(size=(12, 3))
    Xd = rngd.normal(size=(12, 4, 3))
    yd = np.sign(np.einsum("nmp,np->nm", Xd, td))
    objd = make_objective(gd, AgentData(X=Xd, y=yd, mask=np.ones((12, 4))), "logistic", mu=0.3)
    planned_Ti = 3
    cfg = DPConfig(eps_bar=0.8)
    wake = np.concatenate([np.tile(np.arange(12), planned_Ti), np.arange(11)])
    seq = run_private(objd, np.zeros((12, 3)), T=len(wake), cfg=cfg,
                      rng=np.random.default_rng(0), wake_sequence=wake,
                      record_objective=False)
    upd = DPCDUpdate.plan(objd, cfg, planned_Ti=planned_Ti)
    engd = ShardedAsyncEngine(upd, num_shards=4, slot_wakes=12.0, seed=0,
                              relabel="rcm", exchange=ExchangeSpec(method="p2p"))
    st = engd.init_state(np.zeros((12, 3)))
    for _ in range(5):
        st = engd.step(st, np.ones(12, bool))
    counts = engd.part.unpad_rows(np.asarray(st.ustate))
    assert np.array_equal(counts, np.full(12, planned_Ti)), counts
    np.testing.assert_allclose(upd.eps_spent(counts), seq.eps_spent, rtol=1e-10)
    # Spent agents freeze: params and messages stop moving.
    frozen = engd.global_theta(st)
    msgs = float(np.asarray(st.messages).sum())
    st = engd.step(st, np.ones(12, bool))
    assert np.array_equal(engd.global_theta(st), frozen)
    assert float(np.asarray(st.messages).sum()) == msgs
    print("DP_PARITY_OK")
    """
)


FIXED_POINT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import AgentData, knn_graph, make_objective
    from repro.sim import CDUpdate, ExchangeSpec, ShardedAsyncEngine

    rng = np.random.default_rng(0)
    n, p, m = 512, 4, 3
    graph = knn_graph(rng.normal(size=(n, 8)), k=8)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    obj = make_objective(graph, data, "quadratic", mu=0.5, mix_mode="sparse")
    star = obj.solve_exact()
    upd = CDUpdate(obj)
    # Cover the exchange/relabel matrix across the shard counts without
    # blowing up runtime: each S exercises a different configuration.
    for S, kw in ((2, {}), (4, dict(relabel="rcm", exchange=ExchangeSpec(method="p2p"))),
                  (8, dict(relabel="rcm", exchange=ExchangeSpec()))):
        eng = ShardedAsyncEngine(upd, num_shards=S, slot_wakes=128.0, seed=3,
                                 dtype=jnp.float64, **kw)
        res = eng.run(np.zeros((n, p)), slots=700)
        err = np.abs(res.Theta - star).max()
        assert err < 1e-5, (S, err)
        # The exact optimum is a fixed point of the sharded super-tick too.
        st = eng.init_state(star)
        st = eng.advance(st, 5)
        drift = np.abs(eng.global_theta(st) - star).max()
        assert drift < 1e-9, (S, drift)
        print(f"S={S} {kw} err={err:.2e} drift={drift:.2e} method={eng.exchange_method}")
    print("FIXED_POINT_OK")
    """
)


def _run_multidev(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=900,
    )


def test_sharded_forced_parity_and_dp_multidevice():
    res = _run_multidev(MULTIDEV_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FORCED_PARITY_OK" in res.stdout and "DP_PARITY_OK" in res.stdout


@pytest.mark.slow
def test_sharded_fixed_point_512_agents_2_4_8_devices():
    """Acceptance: 512-agent fixed-point agreement <= 1e-5 across 2/4/8
    host devices (and the optimum stays a fixed point of the super-tick)."""
    res = _run_multidev(FIXED_POINT_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FIXED_POINT_OK" in res.stdout
