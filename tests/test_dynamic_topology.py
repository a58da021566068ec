"""Dynamic topology: versioned TopologyState, Dada-style edge refresh,
agent arrivals, and the engines' patch/repartition policy.

In-process tests run on the 1 visible CPU device (dynamic mode on a
1-shard mesh must already agree with the single-device engine). The
multi-shard semantics — pre/post-refresh parity across 4 shards, a full
churn + arrival run, and a forced ``patch()``/repartition — run in a
subprocess with 8 XLA host devices, in the ``test_sharded_engine.py``
style, so this process keeps seeing 1 device."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AgentData, knn_graph, make_objective
from repro.core.graph import TopologyState, as_csr, knn_cosine_graph
from repro.sim import (
    ArrivalConfig,
    AsyncEngine,
    CDUpdate,
    DelayConfig,
    EngineConfig,
    GraphUpdate,
    Scenario,
    ShardedAsyncEngine,
)


def _quad_problem(n, p=3, m=3, seed=0, mu=0.5, k=6, targets=None):
    rng = np.random.default_rng(seed)
    graph = knn_graph(rng.normal(size=(n, 6)), k=k)
    if targets is None:
        targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    return make_objective(graph, data, "quadratic", mu=mu, mix_mode="sparse")


# ---------------------------------------------------------------- graph layer


def test_topology_state_roundtrip_and_capacity():
    obj = _quad_problem(24, seed=0)
    csr = as_csr(obj.graph)
    topo = TopologyState.from_csr(csr)
    back = topo.to_csr()
    np.testing.assert_array_equal(back.indptr, csr.indptr)
    np.testing.assert_array_equal(back.indices, csr.indices)
    np.testing.assert_allclose(back.data, csr.data)
    assert topo.capacity >= csr.max_degree()
    assert int(np.asarray(topo.version)) == 0
    # Weighted degrees / live-slot counts agree with the CSR view.
    np.testing.assert_allclose(np.asarray(topo.degrees()), csr.degrees)
    np.testing.assert_array_equal(
        np.asarray(topo.neighbor_counts()), np.diff(csr.indptr)
    )
    with pytest.raises(ValueError, match="capacity"):
        TopologyState.from_csr(csr, capacity=csr.max_degree() - 1)


def test_topology_state_in_jit_edge_ops():
    """The three slot mutators are pure scatters — usable under jit, with
    symmetric effect and a version bump per call."""
    obj = _quad_problem(16, seed=1)
    csr = as_csr(obj.graph)
    topo = TopologyState.from_csr(csr, slack=4)
    i, j = 0, int(csr.neighbors(0)[0])
    rows = jnp.asarray([i])
    cols = jnp.asarray([j])

    @jax.jit
    def mutate(t):
        t = t.with_edge_weights(rows, cols, jnp.asarray([2.5]))
        t = t.deactivate_edges(rows, cols)
        t = t.activate_edges(rows, cols, jnp.asarray([0.75]))
        return t

    out = mutate(topo)
    assert int(np.asarray(out.version)) == 3
    new_csr = out.to_csr()
    nb, w = new_csr.row(i)
    assert w[list(nb).index(j)] == 0.75
    nb_j, w_j = new_csr.row(j)
    assert w_j[list(nb_j).index(i)] == 0.75  # symmetric by construction
    # Everything else untouched.
    dense_before = np.zeros((csr.n, csr.n))
    r = np.repeat(np.arange(csr.n), np.diff(csr.indptr))
    dense_before[r, csr.indices] = csr.data
    dense_after = np.zeros_like(dense_before)
    r2 = np.repeat(np.arange(new_csr.n), np.diff(new_csr.indptr))
    dense_after[r2, new_csr.indices] = new_csr.data
    dense_before[i, j] = dense_before[j, i] = 0.75
    np.testing.assert_allclose(dense_after, dense_before)


def test_apply_edge_updates_grows_capacity_in_multiples_of_8():
    obj = _quad_problem(20, seed=2, k=4)
    topo = TopologyState.from_csr(as_csr(obj.graph))
    cap = topo.capacity
    # Attach row 0 to every other agent: max degree jumps past capacity.
    others = np.arange(1, 20)
    grown = topo.apply_edge_updates(
        add_rows=np.zeros_like(others), add_cols=others, add_vals=np.ones(19)
    )
    assert grown.capacity >= 19 and grown.capacity % 8 == 0
    assert grown.capacity >= cap  # never shrinks
    assert int(np.asarray(grown.version)) == 1
    nb, _ = grown.to_csr().row(0)
    assert set(nb) == set(range(1, 20))


def test_knn_cosine_chunked_matches_unchunked_and_sparse():
    """The streamed (block_rows) top-k must select the same graph as a
    single-slab pass, and sparse=True the same graph again in CSR form."""
    rng = np.random.default_rng(3)
    feats = rng.normal(size=(57, 9))
    dense = knn_cosine_graph(feats, k=7, block_rows=57)
    for block in (1, 8, 13):
        chunked = knn_cosine_graph(feats, k=7, block_rows=block)
        np.testing.assert_allclose(chunked.weights, dense.weights)
    sp = knn_cosine_graph(feats, k=7, block_rows=8, sparse=True)
    np.testing.assert_allclose(sp.to_dense().weights, dense.weights)


# --------------------------------------------------------------- update layer


def test_graph_update_refresh_deterministic_symmetric_connected():
    obj = _quad_problem(40, seed=4)
    csr = as_csr(obj.graph)
    rng = np.random.default_rng(0)
    Theta = rng.normal(size=(40, 3))
    gu = GraphUpdate(every=5, k=5, candidates=6, gamma=2.0, seed=9)
    a = gu.refresh(csr, Theta, round_index=3)
    b = gu.refresh(csr, Theta, round_index=3)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_allclose(a.data, b.data)
    c = gu.refresh(csr, Theta, round_index=4)
    assert not (
        np.array_equal(a.indices, c.indices) and np.allclose(a.data, c.data)
    ), "distinct rounds should draw distinct candidates"
    # Structural invariants: symmetric, no self loops, no orphans.
    dense = np.zeros((40, 40))
    r = np.repeat(np.arange(40), np.diff(a.indptr))
    dense[r, a.indices] = a.data
    np.testing.assert_allclose(dense, dense.T)
    assert np.all(np.diag(dense) == 0)
    assert (np.diff(a.indptr) >= 1).all()


def test_graph_update_allowed_mask_freezes_outside_edges():
    """Edges touching a non-allowed agent pass through frozen (same
    weight), and no new edge may attach to a non-allowed agent."""
    obj = _quad_problem(30, seed=5)
    csr = as_csr(obj.graph)
    Theta = np.random.default_rng(1).normal(size=(30, 3))
    allowed = np.ones(30, bool)
    blocked = [4, 11, 27]
    allowed[blocked] = False
    gu = GraphUpdate(every=1, k=4, candidates=6, gamma=2.0, seed=2)
    out = gu.refresh(csr, Theta, round_index=1, allowed=allowed)

    def edge_set(g, pred):
        rows = np.repeat(np.arange(g.n), np.diff(g.indptr))
        keep = pred(rows, g.indices)
        return {
            (int(i), int(j)): float(v)
            for i, j, v in zip(rows[keep], g.indices[keep], g.data[keep])
        }

    touch = lambda r, c: ~allowed[r] | ~allowed[c]
    assert edge_set(out, touch) == edge_set(csr, touch)
    # Fully-allowed refresh with the same seed/round matches the masked
    # refresh on the allowed<->allowed subgraph rng-stream-stably? Not
    # required — but the masked result must differ somewhere, proving the
    # mask didn't simply freeze the whole graph.
    both = lambda r, c: allowed[r] & allowed[c]
    assert edge_set(out, both) != edge_set(csr, both)


# --------------------------------------------------------- single-device engine


def test_dynamic_engine_no_refresh_matches_static_bitwise():
    """Static anchor: with a GraphUpdate that never fires, the dynamic
    slot path (capacity-padded tiles + consts gather) must reproduce the
    static engine bit-for-bit under forced wakes in f64."""
    obj = _quad_problem(20, seed=0, p=3)
    n, p = obj.n, obj.p
    stat = AsyncEngine(CDUpdate(obj), slot_wakes=6.0, seed=7, dtype=jnp.float64)
    dyn = AsyncEngine(
        CDUpdate(obj),
        config=EngineConfig(
            graph_update=GraphUpdate(every=10**9),
            slot_wakes=6.0,
            seed=7,
            dtype=jnp.float64,
        ),
    )
    assert dyn.dynamic and not stat.dynamic
    ss, sd = stat.init_state(np.zeros((n, p))), dyn.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(0)
    for _ in range(6):
        mask = rng.random(n) < 0.4
        ss = stat.step(ss, mask)
        sd = dyn.step(sd, mask)
    np.testing.assert_array_equal(np.asarray(ss.Theta), np.asarray(sd.Theta))
    assert float(ss.messages) == float(sd.messages)
    assert int(ss.applied) == int(sd.applied)


def test_dynamic_run_fires_refreshes_and_objective_decreases():
    obj = _quad_problem(20, seed=0, p=3)
    gu = GraphUpdate(every=5, k=5, candidates=4, gamma=2.0)
    eng = AsyncEngine(
        CDUpdate(obj),
        config=EngineConfig(slot_wakes=6.0, seed=3, graph_update=gu,
                            dtype=jnp.float64, metrics=True),
    )
    res = eng.run(np.zeros((obj.n, obj.p)), 20, record_every=10)
    counters = eng.topology_counters()
    assert counters["edge_refreshes"] == 3  # slots 5, 10, 15 (not 20)
    assert counters["edges_added"] > 0 or counters["edges_removed"] > 0
    assert res.objective[-1] <= res.objective[0]
    # Dynamic runs surface topology counters through metrics_snapshot.
    _, derived = eng.metrics_snapshot(eng.init_state(np.zeros((obj.n, obj.p))))
    assert "topology_edge_refreshes" in derived


def test_arrivals_detach_then_admit_with_warm_start():
    obj = _quad_problem(20, seed=0, p=3)
    arr = ArrivalConfig(schedule=((5, (18, 19)),), attach_k=3, seed=1)
    eng = AsyncEngine(
        CDUpdate(obj),
        config=EngineConfig(
            slot_wakes=6.0, seed=3, dtype=jnp.float64,
            scenario=Scenario(arrival=arr),
            graph_update=GraphUpdate(every=5, k=5, candidates=4, gamma=2.0),
        ),
    )
    st = eng.init_state(np.zeros((obj.n, obj.p)))
    assert list(np.flatnonzero(~np.asarray(st.active))) == [18, 19]
    # Pending agents are edge-detached: their rows have no live edges.
    assert (np.diff(eng._csr.indptr)[[18, 19]] == 0).all()
    res = eng.run(np.zeros((obj.n, obj.p)), 12)
    counters = eng.topology_counters()
    assert counters["arrivals"] == 2
    assert bool(np.asarray(res.active).all())
    # Eq. 16 warm start: arrived rows are live (nonzero) immediately.
    assert (np.abs(res.Theta[[18, 19]]).sum(axis=1) > 0).all()


def test_warm_arrivals_start_closer_than_cold():
    """The Eq. 16 warm start must land the arriving agents nearer their
    converged parameters than a cold (zero) start, at admission time.

    Targets share a cluster center: the propagation warm start is a
    neighbour average, which only beats zero when the graph-regularized
    solution is smooth across the attachment neighbourhood (iid random
    targets would make the neighbour average uninformative)."""
    rng = np.random.default_rng(6)
    n, p = 24, 3
    targets = rng.normal(size=(1, p)) + 0.15 * rng.normal(size=(n, p))
    obj = _quad_problem(n, seed=6, p=p, targets=targets)
    star = obj.solve_exact()
    ids = (22, 23)

    def admitted_rows(warm):
        arr = ArrivalConfig(schedule=((7, ids),), attach_k=4, seed=1,
                            warm_start=warm)
        eng = AsyncEngine(
            CDUpdate(obj),
            config=EngineConfig(slot_wakes=8.0, seed=3, dtype=jnp.float64,
                                scenario=Scenario(arrival=arr)),
        )
        st = eng.init_state(np.zeros((obj.n, obj.p)))
        st = eng.advance(st, 6)  # slots 1..6: arrivals still pending
        st = eng.admit(st, list(ids))
        return np.asarray(st.Theta)[list(ids)]

    warm, cold = admitted_rows(True), admitted_rows(False)
    assert np.allclose(cold, 0.0)
    d_warm = np.linalg.norm(warm - star[list(ids)])
    d_cold = np.linalg.norm(cold - star[list(ids)])
    assert d_warm < d_cold


def test_dada_refresh_beats_fixed_graph_on_clustered_targets():
    """Dada-style joint optimization (arXiv 1901.08460): on clustered
    targets with an uninformative initial graph, refreshing edges by
    model similarity must end nearer the true targets than the fixed
    graph, which keeps averaging across clusters."""
    rng = np.random.default_rng(8)
    n, p, m = 32, 3, 2
    centers = np.stack([np.ones(p), -np.ones(p)])
    labels = np.arange(n) % 2
    targets = centers[labels] + 0.1 * rng.normal(size=(n, p))
    obj = _quad_problem(n, p=p, m=m, seed=8, mu=0.4, targets=targets)

    def final_error(gu):
        eng = AsyncEngine(
            CDUpdate(obj),
            config=EngineConfig(slot_wakes=float(n), seed=5,
                                dtype=jnp.float64, graph_update=gu),
        )
        res = eng.run(np.zeros((n, p)), 60)
        return float(np.linalg.norm(res.Theta - targets, axis=1).mean())

    fixed = final_error(GraphUpdate(every=10**9))
    dada = final_error(GraphUpdate(every=5, k=6, candidates=8, gamma=8.0))
    assert dada < fixed, (dada, fixed)


# ------------------------------------------------------------- sharded engine


def test_sharded_dynamic_single_shard_matches_single_device():
    """S=1 dynamic mesh: forced wakes reproduce the single-device dynamic
    engine exactly before any refresh, and the refreshed graphs and
    counters agree through a refresh + further steps."""
    obj = _quad_problem(24, seed=1, p=3)
    n, p = obj.n, obj.p
    cfg = EngineConfig(slot_wakes=6.0, seed=5, dtype=jnp.float64,
                       graph_update=GraphUpdate(every=4, k=5, candidates=4,
                                                gamma=2.0))
    single = AsyncEngine(CDUpdate(obj), config=cfg)
    shard = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, config=cfg)
    ss, sh = single.init_state(np.zeros((n, p))), shard.init_state(np.zeros((n, p)))
    rng = np.random.default_rng(0)
    for _ in range(3):
        mask = rng.random(n) < 0.4
        ss = single.step(ss, mask)
        sh = shard.step(sh, mask)
    np.testing.assert_array_equal(np.asarray(ss.Theta), shard.global_theta(sh))
    # Same refresh on both: identical graphs, then near-identical steps
    # (the capacity-padded gather and the halo gather may sum the same
    # neighbourhood in different orders after a rewire).
    ss = single._refresh_topology(ss, 1)
    sh = shard._refresh_topology(sh, 1)
    np.testing.assert_array_equal(single._csr.indptr, shard._csr.indptr)
    np.testing.assert_array_equal(single._csr.indices, shard._csr.indices)
    np.testing.assert_allclose(single._csr.data, shard._csr.data)
    for _ in range(3):
        mask = rng.random(n) < 0.4
        ss = single.step(ss, mask)
        sh = shard.step(sh, mask)
    np.testing.assert_allclose(
        np.asarray(ss.Theta), shard.global_theta(sh), atol=1e-12, rtol=0.0
    )
    assert shard.topology_counters()["edge_refreshes"] == 1


def test_sharded_set_topology_policy_counters():
    """Weight-only retile, structural patch, and the drift-forced full
    repartition each land in their own counter."""
    obj = _quad_problem(24, seed=2, p=3)
    n, p = obj.n, obj.p
    base = EngineConfig(slot_wakes=6.0, seed=5, dtype=jnp.float64,
                        graph_update=GraphUpdate(every=4))
    eng = ShardedAsyncEngine(CDUpdate(obj), num_shards=1, config=base)
    st = eng.init_state(np.zeros((n, p)))
    # 1) same structure, new weights -> weight patch.
    csr = eng._csr
    reweighted = type(csr)(
        indptr=csr.indptr, indices=csr.indices, data=csr.data * 2.0
    )
    st = eng.set_topology(st, reweighted)
    assert eng.topology_counters()["weight_patches"] == 1
    # 2) structural change under the drift threshold -> structural patch.
    gu = GraphUpdate(every=1, k=5, candidates=2, gamma=1.0)
    st = eng.set_topology(st, gu.refresh(eng._csr, np.zeros((n, p))))
    assert eng.topology_counters()["structural_patches"] == 1
    # 3) negative threshold forces the full rebuild path.
    forced = ShardedAsyncEngine(
        CDUpdate(obj), num_shards=1, config=base.replace(drift_threshold=-10.0)
    )
    st2 = forced.init_state(np.zeros((n, p)))
    st2 = forced._refresh_topology(st2, 1)
    assert forced.topology_counters()["repartitions"] == 1
    st2 = forced.step(st2, np.ones(n, bool))
    assert np.isfinite(forced.global_theta(st2)).all()


def test_dynamic_mode_rejects_unsupported_configs():
    obj = _quad_problem(16, seed=3)
    gu = GraphUpdate(every=4)
    with pytest.raises(NotImplementedError, match="delay"):
        AsyncEngine(
            CDUpdate(obj),
            config=EngineConfig(
                graph_update=gu,
                scenario=Scenario(delay=DelayConfig(max_delay=1)),
            ),
        )
    # A prebuilt partition cannot be reused once arrivals detach edges.
    from repro.sim import partition_graph

    part = partition_graph(as_csr(obj.graph), 1)
    arr = ArrivalConfig(schedule=((2, (15,)),))
    with pytest.raises(ValueError, match="partition"):
        ShardedAsyncEngine(
            CDUpdate(obj), num_shards=1,
            config=EngineConfig(partition=part,
                                scenario=Scenario(arrival=arr)),
        )
    # Topology swaps validate shape and connectivity of non-pending rows.
    eng = AsyncEngine(CDUpdate(obj), config=EngineConfig(graph_update=gu))
    st = eng.init_state(np.zeros((obj.n, obj.p)))
    with pytest.raises(ValueError):
        eng.set_topology(as_csr(_quad_problem(8, seed=0).graph))
    # ... and reject any swap that orphans an established agent (Eq. 4 /
    # Eq. 16 divide by the degree the moment the agent wakes).
    csr = as_csr(obj.graph)
    rows, cols, vals = csr.row_ids(), csr.indices, csr.data
    keep = (rows != 0) & (cols != 0)
    from repro.core.graph import csr_from_coo

    orphaned = csr_from_coo(obj.n, rows[keep], cols[keep], vals[keep])
    with pytest.raises(ValueError, match="no neighbours"):
        eng.set_topology(orphaned)


# ------------------------------------------------------- 8-device subprocess

MULTIDEV_DYNAMIC_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np, jax.numpy as jnp
    from repro.core import AgentData, knn_graph, make_objective
    from repro.sim import (ArrivalConfig, AsyncEngine, CDUpdate, ChurnConfig,
                           EngineConfig, GraphUpdate, Scenario,
                           ShardedAsyncEngine)

    assert len(jax.devices()) == 8

    def prob(n=48, p=3, m=3, seed=0):
        rng = np.random.default_rng(seed)
        graph = knn_graph(rng.normal(size=(n, 6)), k=6)
        targets = rng.normal(size=(n, p)) / np.sqrt(p)
        X = rng.normal(size=(n, m, p)) / np.sqrt(p)
        y = np.einsum("nmp,np->nm", X, targets)
        data = AgentData(X=X, y=y, mask=np.ones((n, m)))
        return make_objective(graph, data, "quadratic", mu=0.5, mix_mode="sparse")

    obj = prob()
    n, p = obj.n, obj.p
    T0 = np.zeros((n, p))
    gu = GraphUpdate(every=4, k=6, candidates=4, gamma=2.0)
    arr = ArrivalConfig(schedule=((6, (46, 47)),), attach_k=3, seed=1)
    cfg = EngineConfig(slot_wakes=8.0, seed=5, dtype=jnp.float64,
                       graph_update=gu, scenario=Scenario(arrival=arr),
                       drift_threshold=0.25)

    # 1) Forced-wake parity, single vs 4 shards, dynamic mode: exact
    #    before any refresh; identical refreshed graphs; tiny-atol equal
    #    after (gather order inside a rewired row may differ).
    single = AsyncEngine(CDUpdate(obj), config=cfg)
    shard = ShardedAsyncEngine(CDUpdate(obj), num_shards=4, config=cfg)
    ss, sh = single.init_state(T0), shard.init_state(T0)
    rng = np.random.default_rng(0)
    for _ in range(3):
        mask = rng.random(n) < 0.4
        ss = single.step(ss, mask); sh = shard.step(sh, mask)
    pre = np.abs(np.asarray(ss.Theta) - shard.global_theta(sh)).max()
    assert pre == 0.0, pre
    ss = single._refresh_topology(ss, 1)
    sh = shard._refresh_topology(sh, 1)
    assert np.array_equal(single._csr.indptr, shard._csr.indptr)
    assert np.array_equal(single._csr.indices, shard._csr.indices)
    assert np.allclose(single._csr.data, shard._csr.data)
    for _ in range(3):
        mask = rng.random(n) < 0.4
        ss = single.step(ss, mask); sh = shard.step(sh, mask)
    post = np.abs(np.asarray(ss.Theta) - shard.global_theta(sh)).max()
    assert post < 1e-12, post
    print("DYNAMIC_PARITY_OK")

    # 2) Full sampled run: churn + refreshes + arrivals on 4 shards.
    run_cfg = EngineConfig(slot_wakes=8.0, seed=5, dtype=jnp.float64,
                           graph_update=gu,
                           scenario=Scenario(arrival=arr,
                                             churn=ChurnConfig(leave_prob=0.05)),
                           drift_threshold=0.25)
    eng = ShardedAsyncEngine(CDUpdate(obj), num_shards=4, config=run_cfg)
    res = eng.run(T0, 16, record_every=8)
    c = eng.topology_counters()
    assert c["edge_refreshes"] == 3, c
    assert c["arrivals"] == 2, c
    assert c["weight_patches"] + c["structural_patches"] + c["repartitions"] > 0, c
    assert np.isfinite(res.Theta).all()
    assert res.objective[-1] <= res.objective[0]
    print("DYNAMIC_RUN_OK")

    # 3) Forced repartition: drift threshold below any drift makes every
    #    structural swap a full rebuild + state re-layout.
    eng2 = ShardedAsyncEngine(CDUpdate(obj), num_shards=4,
                              config=cfg.replace(drift_threshold=-10.0,
                                                 scenario=None))
    st = eng2.init_state(T0)
    st = eng2._refresh_topology(st, 1)
    assert eng2.topology_counters()["repartitions"] == 1
    st = eng2.step(st, np.random.default_rng(1).random(n) < 0.4)
    assert np.isfinite(eng2.global_theta(st)).all()
    print("REPARTITION_OK")
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


def test_sharded_dynamic_topology_multidevice():
    res = _run_multidev(MULTIDEV_DYNAMIC_SCRIPT)
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("DYNAMIC_PARITY_OK", "DYNAMIC_RUN_OK", "REPARTITION_OK"):
        assert marker in res.stdout, res.stdout
