"""CSR graph backend: construction, invariants, and dense/sparse parity."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    AgentData,
    CSRGraph,
    as_csr,
    csr_from_coo,
    knn_cosine_graph,
    knn_graph,
    make_objective,
    mix_op,
    neighbor_counts,
    random_geometric_graph,
    ring_graph,
    run_propagation,
    run_scan,
    sparse_crossover,
    synchronous_round,
)
from repro.core.graph import dense_weights
from repro.core.mixing import mix_edge_block
from repro.data.synthetic import linear_classification_problem


def _quad_objectives(n=12, p=6, mu=0.5, seed=3):
    prob = linear_classification_problem(n=n, p=p, m_low=5, m_high=15, seed=seed)
    X = prob.train.X
    y = np.einsum("nmp,np->nm", X, prob.targets) * prob.train.mask
    data = AgentData(X=X, y=y, mask=prob.train.mask)
    dense = make_objective(prob.graph, data, "quadratic", mu=mu, mix_mode="dense")
    sparse = make_objective(
        prob.graph.to_csr(), data, "quadratic", mu=mu, mix_mode="sparse"
    )
    return dense, sparse


# ---------------------------------------------------------------------------
# Construction and invariants
# ---------------------------------------------------------------------------


def test_csr_roundtrip_preserves_weights():
    g = ring_graph(9, weight=1.5)
    back = g.to_csr().to_dense()
    np.testing.assert_allclose(back.weights, g.weights)


def test_csr_matches_dense_accessors():
    feats = np.random.default_rng(0).normal(size=(40, 8))
    gd = knn_cosine_graph(feats, k=4)
    gs = gd.to_csr()
    assert gs.n == gd.n
    assert gs.num_edges() == gd.num_edges()
    assert gs.max_degree() == gd.max_degree()
    np.testing.assert_allclose(gs.degrees, gd.degrees)
    np.testing.assert_array_equal(neighbor_counts(gs), neighbor_counts(gd))
    for i in range(gd.n):
        np.testing.assert_array_equal(gs.neighbors(i), gd.neighbors(i))
    assert gs.is_connected() == gd.is_connected()


def test_csr_rejects_asymmetry():
    with pytest.raises(ValueError, match="symmetric"):
        CSRGraph(
            indptr=np.array([0, 1, 1]),
            indices=np.array([1], dtype=np.int32),
            data=np.array([1.0]),
        )


def test_csr_rejects_self_loops_and_negative_weights():
    with pytest.raises(ValueError, match="diagonal"):
        CSRGraph(
            indptr=np.array([0, 1]),
            indices=np.array([0], dtype=np.int32),
            data=np.array([1.0]),
        )
    with pytest.raises(ValueError, match="non-negative"):
        csr_from_coo(2, [0, 1], [1, 0], [-1.0, -1.0])


def test_csr_from_coo_dedupes_and_symmetrizes():
    g = csr_from_coo(3, [0, 0, 1], [1, 1, 2], [0.5, 2.0, 1.0], symmetrize=True)
    np.testing.assert_allclose(
        dense_weights(g), [[0, 2.0, 0], [2.0, 0, 1.0], [0, 1.0, 0]]
    )


def test_knn_graph_matches_dense_knn():
    feats = np.random.default_rng(1).normal(size=(64, 10))
    want = knn_cosine_graph(feats, k=5).weights
    got = dense_weights(knn_graph(feats, k=5, block_rows=7))
    np.testing.assert_allclose(got, want)


def test_knn_clamps_k_to_everyone_is_a_neighbour():
    """k >= n must mean the complete graph (paper semantics), not an
    np.argpartition crash on an out-of-range kth."""
    feats = np.random.default_rng(3).normal(size=(5, 4))
    for k in (4, 5, 17):  # n - 1, n, and far beyond
        dense = knn_cosine_graph(feats, k=k)
        sparse = knn_graph(feats, k=k, block_rows=2)
        want = 1.0 - np.eye(5)
        np.testing.assert_array_equal(dense.weights, want)
        np.testing.assert_array_equal(dense_weights(sparse), want)


def test_knn_degenerate_single_agent():
    feats = np.ones((1, 3))
    assert knn_cosine_graph(feats, k=10).num_edges() == 0
    g = knn_graph(feats, k=10)
    assert g.n == 1 and g.nnz == 0


def test_random_geometric_graph_properties():
    rng = np.random.default_rng(2)
    g = random_geometric_graph(800, rng, avg_degree=10.0)
    deg = neighbor_counts(g)
    assert deg.min() >= 1  # Eq. 4 divides by D_ii
    assert 4.0 < deg.mean() < 20.0  # near the target, MC slack
    g.to_dense()  # validates symmetry/diagonal via AgentGraph checks


def test_padded_neighbors_covers_all_edges():
    g = as_csr(ring_graph(7, weight=2.0))
    idx, w = g.padded_neighbors(pad_to=5)
    assert idx.shape == (7, 5) and w.shape == (7, 5)
    np.testing.assert_allclose(w.sum(axis=1), g.degrees)  # pad weight 0
    # Pad entries point at the row itself: gathers always in-bounds.
    assert idx.min() >= 0 and idx.max() < 7


def test_sparse_crossover_env_knob(monkeypatch):
    monkeypatch.setenv("REPRO_SPARSE_CROSSOVER", "3")
    assert sparse_crossover() == 3
    g = ring_graph(5)
    assert mix_op(g, mode="auto").kind == "sparse"
    monkeypatch.setenv("REPRO_SPARSE_CROSSOVER", "1000")
    assert mix_op(g, mode="auto").kind == "dense"


def test_kernel_max_n_env_knob(monkeypatch):
    import jax

    from repro.core.mixing import kernel_max_n

    monkeypatch.setenv("REPRO_KERNEL_MAX_N", "7")
    assert kernel_max_n() == 7
    monkeypatch.setenv("REPRO_KERNEL_MAX_N", "not-a-number")
    with pytest.raises(ValueError):
        kernel_max_n()
    monkeypatch.delenv("REPRO_KERNEL_MAX_N")
    assert kernel_max_n() == 4096  # default

    # The auto-gate honours the knob (simulate a TPU backend; dtype f32).
    op = mix_op(ring_graph(16), mode="sparse")
    theta = jnp.zeros((16, 4), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op._kernel_auto(theta)
    monkeypatch.setenv("REPRO_KERNEL_MAX_N", "8")
    assert not op._kernel_auto(theta)  # n=16 now above the ceiling
    assert not op._kernel_auto(theta.astype(jnp.float64))  # dtype gate intact


# ---------------------------------------------------------------------------
# Dense/sparse parity of the operators and full algorithms
# ---------------------------------------------------------------------------


def test_mix_operator_parity():
    rng = np.random.default_rng(3)
    g = knn_cosine_graph(rng.normal(size=(50, 8)), k=6)
    Theta = jnp.asarray(rng.normal(size=(50, 17)), jnp.float32)
    dense, sparse = mix_op(g, mode="dense"), mix_op(g, mode="sparse")
    np.testing.assert_allclose(
        np.asarray(dense.all(Theta)), np.asarray(sparse.all(Theta)), atol=1e-5
    )
    for i in [0, 7, 49]:
        np.testing.assert_allclose(
            np.asarray(dense.row(Theta, i)), np.asarray(sparse.row(Theta, i)), atol=1e-5
        )
    np.testing.assert_allclose(
        float(dense.pairwise_smoothness(Theta)),
        float(sparse.pairwise_smoothness(Theta)),
        rtol=1e-6,
    )


def _hub_knn_graph(n, hubs, hub_degree, seed):
    """A star of ``hub_degree`` spokes at each of the first ``hubs`` agents,
    joined to a 6-NN cosine graph: a few rows far wider than the rest."""
    rng = np.random.default_rng(seed)
    knn = as_csr(knn_cosine_graph(rng.normal(size=(n, 8)), k=6))
    spokes = [rng.choice(np.arange(hubs, n), hub_degree, replace=False) for _ in range(hubs)]
    rows = np.concatenate([knn.row_ids(), np.repeat(np.arange(hubs), hub_degree)])
    cols = np.concatenate([knn.indices, *spokes])
    return csr_from_coo(n, rows, cols, np.ones(len(rows)), symmetrize=True)


def _uniform_block(op, B):
    """The CSR gather's edge block for B rows drawn uniformly."""
    return mix_edge_block(np.diff(op.indptr), np.full(op.n, B / op.n), B)


@pytest.mark.parametrize(
    "case", ["dense", "sparse", "kernel", "hub", "sentinel", "two_trips"]
)
def test_mix_gather_rows_batched_parity(case):
    """gather_rows (the repro.sim woken-rows path) == stacked row() calls:
    on both backends, the interpreted kernel route against the plain one,
    and the CSR edge gather on a hub graph (where B·K is far more than the
    rows' real edges), with the padding sentinel n among the rows (whose
    sums are 0), and with more edges than one block holds (two trips or
    more of its loop)."""
    rng = np.random.default_rng(8)
    if case in ("dense", "sparse", "kernel"):
        g = knn_cosine_graph(rng.normal(size=(40, 8)), k=6)
        Theta = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
        rows = jnp.asarray([0, 3, 17, 39, 5])
        if case == "kernel":
            sparse = mix_op(g, mode="sparse")
            block = _uniform_block(sparse, len(rows))
            kern = np.asarray(sparse.gather_rows(Theta, rows, use_kernel=True)[0])
            plain = np.asarray(sparse.gather_rows(Theta, rows, use_kernel=False, edge_block=block)[0])
            np.testing.assert_allclose(kern, plain, rtol=1e-5, atol=1e-5)
            return
        op = mix_op(g, mode=case)
        block = _uniform_block(op, len(rows)) if case == "sparse" else None
        got = np.asarray(op.gather_rows(Theta, rows, edge_block=block)[0])
        want = np.stack([np.asarray(op.row(Theta, int(i))) for i in np.asarray(rows)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        return

    n = 400
    g = _hub_knn_graph(n, hubs=3, hub_degree=250, seed=8)
    op = mix_op(g, mode="sparse")
    deg = np.diff(g.indptr)
    Theta = jnp.asarray(rng.normal(size=(n, 9)), jnp.float32)
    rows = np.array([0, 3, 17, 399, 5, 250, 1])
    if case == "hub":
        rows = np.concatenate([[0], rng.choice(np.arange(3, n), 19, replace=False)])
    elif case == "sentinel":
        rows = np.array([0, n, 17, n, 399, 5, n])
    block = 128 if case == "two_trips" else _uniform_block(op, len(rows))
    got, edges, trips = op.gather_rows(Theta, jnp.asarray(rows), edge_block=block)
    real = rows[rows < n]
    assert int(edges) == int(deg[real].sum())
    K = int(deg.max())
    if case == "hub":
        assert len(rows) * K > 10 * int(edges)  # the padded block's slots
    if case == "two_trips":
        assert int(trips) == -(-int(edges) // block) >= 2
    else:
        assert int(trips) == 1
    zero = np.zeros(Theta.shape[1], np.float32)
    want = np.stack(
        [np.asarray(op.row(Theta, int(i))) if i < n else zero for i in rows]
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


def test_mix_all_kernel_path_parity():
    """MixOp.all(use_kernel=True) (Pallas, interpreted on CPU) == jnp path,
    for both backends; auto stays off the kernels on a CPU backend."""
    import jax

    rng = np.random.default_rng(11)
    g = knn_cosine_graph(rng.normal(size=(48, 8)), k=5)
    Theta = jnp.asarray(rng.normal(size=(48, 130)), jnp.float32)
    for mode in ("dense", "sparse"):
        op = mix_op(g, mode=mode)
        np.testing.assert_allclose(
            np.asarray(op.all(Theta, use_kernel=True)),
            np.asarray(op.all(Theta, use_kernel=False)),
            rtol=1e-5, atol=1e-5,
        )
        if jax.default_backend() != "tpu":
            assert not op._kernel_auto(Theta)


def test_objective_value_and_grad_parity():
    obj_d, obj_s = _quad_objectives()
    rng = np.random.default_rng(4)
    Theta = jnp.asarray(rng.normal(size=(obj_d.n, obj_d.p)))
    assert abs(float(obj_d.value(Theta)) - float(obj_s.value(Theta))) < 1e-8
    np.testing.assert_allclose(
        np.asarray(obj_d.block_grad(Theta)), np.asarray(obj_s.block_grad(Theta)),
        atol=1e-8,
    )
    np.testing.assert_allclose(obj_d.solve_exact(), obj_s.solve_exact(), atol=1e-10)


def test_cd_trajectory_parity_dense_vs_sparse():
    obj_d, obj_s = _quad_objectives()
    rng = np.random.default_rng(5)
    wake = rng.integers(0, obj_d.n, size=150)
    rd = run_scan(obj_d, np.zeros((obj_d.n, obj_d.p)), T=150, rng=rng, wake_sequence=wake)
    rs = run_scan(obj_s, np.zeros((obj_s.n, obj_s.p)), T=150, rng=rng, wake_sequence=wake)
    np.testing.assert_allclose(rd.Theta, rs.Theta, atol=1e-5)
    np.testing.assert_allclose(rd.objective, rs.objective, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(rd.messages, rs.messages)


def test_synchronous_round_parity():
    obj_d, obj_s = _quad_objectives()
    rng = np.random.default_rng(6)
    Theta = jnp.asarray(rng.normal(size=(obj_d.n, obj_d.p)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(synchronous_round(obj_d, Theta)),
        np.asarray(synchronous_round(obj_s, Theta)),
        atol=1e-5,
    )


def test_model_propagation_parity():
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(20, 6))
    gd = knn_cosine_graph(feats, k=4)
    theta = rng.normal(size=(20, 5))
    out_d = run_propagation(gd, theta.copy(), 0.5, np.ones(20), 60, np.random.default_rng(8))
    out_s = run_propagation(
        gd.to_csr(), theta.copy(), 0.5, np.ones(20), 60, np.random.default_rng(8)
    )
    np.testing.assert_allclose(out_d, out_s, atol=1e-12)


def test_gossip_gather_matches_gossip_dense():
    from repro.core.spmd import gossip_dense, gossip_gather

    rng = np.random.default_rng(9)
    A, K = 8, 2
    params = {"w": jnp.asarray(rng.normal(size=(A, 4, 3)), jnp.float32)}
    W = np.zeros((A, A))
    for i in range(A):
        W[i, (i + 1) % A] = W[i, (i - 1) % A] = 1.0
    mix_mat = jnp.asarray(W / W.sum(1, keepdims=True), jnp.float32)
    idx = np.stack([(np.arange(A) + 1) % A, (np.arange(A) - 1) % A], axis=1)
    w = jnp.full((A, K), 0.5, jnp.float32)
    out_d = gossip_dense(params, mix_mat)
    out_s = gossip_gather(params, jnp.asarray(idx, jnp.int32), w)
    np.testing.assert_allclose(
        np.asarray(out_d["w"]), np.asarray(out_s["w"]), atol=1e-6
    )
