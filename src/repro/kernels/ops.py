"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` compiles the kernels for the TPU when JAX's backend is
a TPU and interprets them anywhere else (the CPU test runs). The mixing
gate engages the kernels only on a TPU, so interpretation never stands
in for the device on a program path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dp_clip_noise as _dpk
from repro.kernels import graph_mix as _gmk
from repro.kernels import sparse_mix as _smk
from repro.kernels import ssm_scan as _ssk


def _default_interpret():
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("clip", "noise_scale", "block_n", "block_d", "interpret")
)
def dp_clip_noise(grads, noise, clip, noise_scale, block_n=128, block_d=512, interpret=None):
    """Fused per-example clip -> mean -> noise. grads (N,D), noise (D,) -> (D,)."""
    interpret = _default_interpret() if interpret is None else interpret
    N, D = grads.shape
    bn = min(block_n, max(8, N))
    bd = min(block_d, max(128, D))
    g = _pad_to(_pad_to(grads, bn, 0), bd, 1)
    nz = _pad_to(noise, bd, 0)
    # zero-padded rows have zero norm/zero grad: they do not affect the mean
    # because the kernel divides by the true N.
    out = _dpk.dp_clip_noise(
        g, nz, clip, noise_scale, block_n=bn, block_d=bd, interpret=interpret, n_true=N
    )
    return out[:D]


@functools.partial(jax.jit, static_argnames=("block_p", "block_k", "interpret"))
def graph_mix(mix, theta, block_p=128, block_k=128, interpret=None):
    """Y = mix @ theta. mix (n,n), theta (n,p) -> (n,p) float32.

    The (n, block_p) output tile stays VMEM-resident across the
    contraction, so its size, not p, bounds the VMEM the kernel needs.
    """
    interpret = _default_interpret() if interpret is None else interpret
    n, p = theta.shape
    bp = min(block_p, max(128, p))
    t = _pad_to(theta, bp, 1)
    if n > block_k:
        # Zero-pad the contraction to whole tiles: a partial tile would
        # read past the arrays' ends and add what it finds there.
        mix = _pad_to(mix, block_k, 1)
        t = _pad_to(t, block_k, 0)
    out = _gmk.graph_mix(mix, t, block_p=bp, block_k=block_k, interpret=interpret)
    return out[:, :p]


@functools.partial(jax.jit, static_argnames=("block_a", "block_p", "interpret"))
def sparse_mix(idx, w, theta, block_a=8, block_p=256, interpret=None):
    """Y[i] = sum_k w[i,k] theta[idx[i,k]]. idx/w (n,K), theta (n,p) -> (n,p) f32."""
    interpret = _default_interpret() if interpret is None else interpret
    n, p = theta.shape
    bp = min(block_p, max(128, p))
    with jax.named_scope("obs.sparse_mix"):
        t = _pad_to(theta, bp, 1)
        out = _smk.sparse_mix(idx, w, t, block_a=block_a, block_p=bp, interpret=interpret)
        return out[:, :p]


# Woken-rows neighbour mix: Y[b] = sum_k w[b,k] theta[idx[b,k]] for (B, K)
# tiles already gathered down to the rows that woke this super-tick. The
# generalized kernel makes the row batch independent of n, so this IS
# sparse_mix; the alias marks the repro.sim call sites and keeps the two
# paths from ever diverging.
sparse_rows_mix = sparse_mix


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_chunk(C, B, cum, dt, x, interpret=None):
    """Mamba2 intra-chunk SSD. See repro.kernels.ssm_scan."""
    interpret = _default_interpret() if interpret is None else interpret
    return _ssk.ssm_chunk(C, B, cum, dt, x, interpret=interpret)


# Differentiable variant: Pallas kernel on the forward pass, oracle VJP on
# the backward pass (standard practice until a hand-written bwd kernel
# lands; the bwd is the same einsum family and XLA fuses it well).
@jax.custom_vjp
def ssm_chunk_ad(C, B, cum, dt, x):
    return ssm_chunk(C, B, cum, dt, x)


def _ssm_chunk_fwd(C, B, cum, dt, x):
    from repro.kernels import ref as _ref

    out = ssm_chunk(C, B, cum, dt, x)
    return out, (C, B, cum, dt, x)


def _ssm_chunk_bwd(res, g):
    from repro.kernels import ref as _ref

    _, vjp = jax.vjp(_ref.ssm_chunk_ref, *res)
    return vjp(g)


ssm_chunk_ad.defvjp(_ssm_chunk_fwd, _ssm_chunk_bwd)
