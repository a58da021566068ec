"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dp_clip_noise_ref(grads, noise, clip, noise_scale):
    """Fused DP gradient aggregation (Eq. 6 + Supp. D.2 clipping).

    grads: (N, D) per-example gradients; noise: (D,) standard-Laplace draws;
    clip: L2 clip constant C; noise_scale: Laplace scale s (already includes
    2 L0 / (eps m)). Returns (D,) = mean_i clip(g_i) + s * noise.
    """
    g32 = grads.astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(g32**2, axis=-1, keepdims=True))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))
    mean = jnp.mean(g32 * scale, axis=0)
    return mean + noise_scale * noise.astype(jnp.float32)


def graph_mix_ref(mix, theta):
    """Neighbour mixing: Y = A @ Theta. mix: (n, n); theta: (n, p)."""
    return (mix.astype(jnp.float32) @ theta.astype(jnp.float32)).astype(theta.dtype)


def sparse_mix_ref(idx, w, theta):
    """Padded-neighbour mixing: Y[r] = sum_k w[r,k] Theta[idx[r,k]].

    idx: (R, K) int32; w: (R, K); theta: (n, p). Pad entries carry weight 0.
    R == n is the full neighbour sum; R == B < n is the woken-rows batch
    (``sparse_rows_mix``), which shares this oracle.
    """
    gathered = theta.astype(jnp.float32)[idx]  # (R, K, p)
    return jnp.einsum("nk,nkp->np", w.astype(jnp.float32), gathered)


sparse_rows_mix_ref = sparse_mix_ref


def csr_mix_ref(rows, cols, vals, theta, n):
    """CSR neighbour mixing as a pure segment_sum (the O(nnz) oracle).

    rows/cols/vals: (nnz,) sorted COO triples of the symmetric W;
    theta: (n, p). Returns (n, p) = sum over stored entries.
    """
    contrib = vals.astype(jnp.float32)[:, None] * theta.astype(jnp.float32)[cols]
    return jax.ops.segment_sum(contrib, rows, num_segments=n, indices_are_sorted=True)


def ssm_chunk_ref(C, B, cum, dt, x):
    """Mamba2 intra-chunk SSD (single head-group block).

    C, B: (G, Q, N); cum: (G, Q) inclusive cumulative log-decay;
    dt: (G, Q); x: (G, Q, P).
    Returns:
      y:     (G, Q, P)  causal intra-chunk output
      s_loc: (G, P, N)  chunk-local end state
    """
    C = C.astype(jnp.float32)
    B = B.astype(jnp.float32)
    cum = cum.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    x = x.astype(jnp.float32)
    Q = C.shape[1]
    cb = jnp.einsum("gqn,gtn->gqt", C, B)
    decay = jnp.exp(jnp.clip(cum[:, :, None] - cum[:, None, :], -60.0, 0.0))
    causal = jnp.tril(jnp.ones((Q, Q), dtype=bool))
    scores = jnp.where(causal[None], cb * decay * dt[:, None, :], 0.0)
    y = jnp.einsum("gqt,gtp->gqp", scores, x)
    w_end = jnp.exp(jnp.clip(cum[:, -1:] - cum, -60.0, 0.0)) * dt  # (G,Q)
    s_loc = jnp.einsum("gq,gqp,gqn->gpn", w_end, x, B)
    return y, s_loc
