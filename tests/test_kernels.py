"""Per-kernel allclose sweeps: Pallas (interpret mode on CPU) vs ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# dp_clip_noise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D", [(8, 128), (33, 200), (128, 512), (200, 1000), (1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dp_clip_noise_matches_ref(N, D, dtype):
    rng = np.random.default_rng(N * 1000 + D)
    grads = jnp.asarray(rng.normal(size=(N, D)) * 3.0, dtype)
    noise = jnp.asarray(rng.laplace(size=(D,)), jnp.float32)
    clip, s = 1.5, 0.37
    got = ops.dp_clip_noise(grads, noise, clip, s, interpret=True)
    want = ref.dp_clip_noise_ref(grads, noise, clip, s)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_dp_clip_noise_clips_every_row():
    """Property: with zero noise the output norm is bounded by the clip."""
    rng = np.random.default_rng(0)
    grads = jnp.asarray(rng.normal(size=(16, 256)) * 100.0, jnp.float32)
    out = ops.dp_clip_noise(grads, jnp.zeros((256,)), 1.0, 0.0, interpret=True)
    assert float(jnp.linalg.norm(out)) <= 1.0 + 1e-5


@pytest.mark.parametrize("block_n,block_d", [(8, 128), (64, 256), (128, 1024)])
def test_dp_clip_noise_block_shape_invariance(block_n, block_d):
    rng = np.random.default_rng(7)
    grads = jnp.asarray(rng.normal(size=(77, 300)), jnp.float32)
    noise = jnp.asarray(rng.laplace(size=(300,)), jnp.float32)
    got = ops.dp_clip_noise(grads, noise, 2.0, 0.1, block_n=block_n, block_d=block_d,
                            interpret=True)
    want = ref.dp_clip_noise_ref(grads, noise, 2.0, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# graph_mix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,p", [(4, 128), (16, 100), (100, 300), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_mix_matches_ref(n, p, dtype):
    rng = np.random.default_rng(n + p)
    mix = jnp.asarray(rng.random((n, n)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=(n, p)), dtype)
    got = ops.graph_mix(mix, theta, interpret=True)
    want = ref.graph_mix_ref(mix, theta).astype(jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_graph_mix_identity():
    theta = jnp.asarray(np.random.default_rng(1).normal(size=(32, 257)), jnp.float32)
    got = ops.graph_mix(jnp.eye(32), theta, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(theta), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# sparse_mix
# ---------------------------------------------------------------------------


def _random_padded_graph(n, k, rng):
    """Padded (idx, w) neighbour tiles of a random symmetric graph."""
    from repro.core import knn_cosine_graph

    csr = knn_cosine_graph(rng.normal(size=(n, 8)), k=k).to_csr()
    idx, w = csr.padded_neighbors()
    return jnp.asarray(idx), jnp.asarray(w, jnp.float32), csr


@pytest.mark.parametrize("n,k,p", [(8, 3, 128), (33, 5, 200), (100, 10, 300), (128, 7, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_mix_matches_refs(n, k, p, dtype):
    rng = np.random.default_rng(n * 100 + p)
    idx, w, csr = _random_padded_graph(n, k, rng)
    theta = jnp.asarray(rng.normal(size=(n, p)), dtype)
    got = ops.sparse_mix(idx, w, theta, interpret=True)
    want_gather = ref.sparse_mix_ref(idx, w, theta)
    want_segsum = ref.csr_mix_ref(
        jnp.asarray(csr.row_ids()), jnp.asarray(csr.indices),
        jnp.asarray(csr.data, jnp.float32), theta, n,
    )
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_gather), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_segsum), rtol=tol, atol=tol)


def test_sparse_mix_agrees_with_dense_graph_mix():
    """The sparse kernel on CSR tiles == the dense kernel on the full matrix."""
    from repro.core.graph import dense_weights

    rng = np.random.default_rng(0)
    idx, w, csr = _random_padded_graph(64, 6, rng)
    theta = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    got = ops.sparse_mix(idx, w, theta, interpret=True)
    want = ops.graph_mix(jnp.asarray(dense_weights(csr), jnp.float32), theta, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_a,block_p", [(8, 128), (16, 256), (64, 512)])
def test_sparse_mix_block_shape_invariance(block_a, block_p):
    rng = np.random.default_rng(7)
    idx, w, _ = _random_padded_graph(50, 4, rng)
    theta = jnp.asarray(rng.normal(size=(50, 300)), jnp.float32)
    got = ops.sparse_mix(idx, w, theta, block_a=block_a, block_p=block_p, interpret=True)
    want = ref.sparse_mix_ref(idx, w, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sparse_mix_pad_entries_contribute_nothing():
    """Rows padded past their true degree (weight 0) must not alter the sum."""
    rng = np.random.default_rng(3)
    _, _, csr = _random_padded_graph(32, 4, rng)
    theta = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    idx_a, w_a = csr.padded_neighbors()
    idx_b, w_b = csr.padded_neighbors(pad_to=idx_a.shape[1] + 5)
    out_a = ops.sparse_mix(jnp.asarray(idx_a), jnp.asarray(w_a, jnp.float32), theta, interpret=True)
    out_b = ops.sparse_mix(jnp.asarray(idx_b), jnp.asarray(w_b, jnp.float32), theta, interpret=True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# sparse_rows_mix (woken-rows batch; the repro.sim super-tick path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 7, 24, 64])
def test_sparse_rows_mix_is_row_slice_of_sparse_mix(B):
    rng = np.random.default_rng(B)
    idx, w, _ = _random_padded_graph(64, 6, rng)
    theta = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    rows = jnp.asarray(rng.choice(64, size=B, replace=False))
    got = ops.sparse_rows_mix(idx[rows], w[rows], theta, interpret=True)
    full = ops.sparse_mix(idx, w, theta, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full)[np.asarray(rows)],
                               rtol=1e-6, atol=1e-6)
    want = ref.sparse_rows_mix_ref(idx[rows], w[rows], theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssm_chunk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("G,Q,N,P", [(2, 16, 8, 16), (4, 64, 64, 64), (1, 128, 64, 64), (3, 32, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_chunk_matches_ref(G, Q, N, P, dtype):
    rng = np.random.default_rng(G * Q + N + P)
    C = jnp.asarray(rng.normal(size=(G, Q, N)), dtype)
    B = jnp.asarray(rng.normal(size=(G, Q, N)), dtype)
    loga = -np.abs(rng.normal(size=(G, Q)) * 0.1)
    cum = jnp.asarray(np.cumsum(loga, axis=1), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(G, Q))) * 0.5, jnp.float32)
    x = jnp.asarray(rng.normal(size=(G, Q, P)), dtype)
    y, s = ops.ssm_chunk(C, B, cum, dt, x, interpret=True)
    yr, sr = ref.ssm_chunk_ref(C, B, cum, dt, x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=tol, atol=tol)


def test_ssm_chunk_causality():
    """Property: output at position q must not depend on inputs at t > q."""
    rng = np.random.default_rng(3)
    G, Q, N, P = 1, 32, 16, 16
    C = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    cum = jnp.asarray(np.cumsum(-np.abs(rng.normal(size=(G, Q)) * 0.1), axis=1), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(G, Q))), jnp.float32)
    x = jnp.asarray(rng.normal(size=(G, Q, P)), jnp.float32)
    y1, _ = ops.ssm_chunk(C, B, cum, dt, x, interpret=True)
    x2 = x.at[:, Q // 2 :].set(999.0)
    y2, _ = ops.ssm_chunk(C, B, cum, dt, x2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y1[:, : Q // 2]), np.asarray(y2[:, : Q // 2]), rtol=1e-6
    )


@pytest.mark.slow
def test_mamba2_kernel_path_matches_einsum_path():
    """use_kernel=True must be numerically identical (fwd) and allclose (bwd)."""
    from repro.configs.base import ModelConfig, SSMConfig
    from repro.models import ssm as ssm_mod

    cfg = ModelConfig(
        name="t", family="hybrid", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=64,
        ssm=SSMConfig(state_dim=8, head_dim=8, conv_kernel=4, chunk=16, expand=2),
    )
    params = ssm_mod.init_mamba2(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 32), jnp.float32)
    y0 = ssm_mod.mamba2_forward(params, x, cfg, use_kernel=False)
    y1 = ssm_mod.mamba2_forward(params, x, cfg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-5, atol=1e-5)
    g0 = jax.grad(lambda p: jnp.sum(ssm_mod.mamba2_forward(p, x, cfg) ** 2))(params)
    g1 = jax.grad(
        lambda p: jnp.sum(ssm_mod.mamba2_forward(p, x, cfg, use_kernel=True) ** 2)
    )(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_ssm_chunk_consistency_with_model_reference():
    """The kernel must agree with the full mamba2_forward intra-chunk math on
    a single-chunk sequence (inter-chunk contribution is zero there)."""
    import dataclasses

    from repro.configs.base import ModelConfig, SSMConfig
    from repro.models import ssm as ssm_mod

    cfg = ModelConfig(
        name="t", family="hybrid", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=64,
        ssm=SSMConfig(state_dim=8, head_dim=8, conv_kernel=4, chunk=16, expand=2),
    )
    key = jax.random.PRNGKey(0)
    params = ssm_mod.init_mamba2(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    out = ssm_mod.mamba2_forward(params, x, cfg)
    assert out.shape == (2, 16, 32)
    assert not bool(jnp.any(jnp.isnan(out)))


