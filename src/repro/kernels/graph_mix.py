"""Pallas TPU kernel: dense neighbour mixing  Y = A @ Theta.

The simulator fast-path and the dense-W SPMD fallback both need the mixing
matrix A = f(W) applied to the stacked agent models Theta (n, p) every
round. n (agents co-resident on a chip) is small — A fits VMEM whole — but
p is the full (sharded) parameter dimension, so Theta streams through in
feature tiles. Grid: (feature_tiles, contraction_tiles) with the (n, bp)
output tile resident in VMEM across the contraction; MXU-aligned 128x128
tiles by default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEF_BP = 128  # feature-tile width
DEF_BK = 128  # contraction tile


def _mix_kernel(a_ref, t_ref, out_ref):
    k = pl.program_id(1)
    a = a_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    partial = jax.lax.dot(a, t, precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(k != 0)
    def _acc():
        out_ref[...] += partial


def graph_mix(mix, theta, block_p=DEF_BP, block_k=DEF_BK, interpret=False):
    """mix: (n, nk) float; theta: (nk, p). Returns (n, p) float32.

    nk (the contraction) is n, or n zero-padded to a multiple of block_k.
    """
    n, nk = mix.shape
    p = theta.shape[1]
    bk = min(block_k, nk)
    bp = min(block_p, p)
    nb_k = pl.cdiv(nk, bk)
    nb_p = pl.cdiv(p, bp)
    return pl.pallas_call(
        _mix_kernel,
        grid=(nb_p, nb_k),
        in_specs=[
            pl.BlockSpec((n, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, bp), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((n, bp), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, p), jnp.float32),
        interpret=interpret,
    )(mix, theta)
