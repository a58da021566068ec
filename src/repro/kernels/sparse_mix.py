"""Pallas TPU kernel: sparse neighbour mixing over padded neighbour tiles.

Computes ``Y[r] = sum_k w[r, k] * Theta[idx[r, k]]`` — the CSR neighbour
sum in padded (R, K) form (K = max degree; pad entries point at the row
itself with weight 0). The row batch R is independent of the agent count
n = Theta.shape[0]: with R == n this is the full neighbour sum (O(n * K * p)
vs the dense ``graph_mix`` kernel's O(n^2 * p) matmul); with R == B << n it
is the woken-rows path of the ``repro.sim`` super-tick, where only the
agents that woke this slot need their neighbourhoods mixed.

Scope: like ``graph_mix``, this kernel serves the *on-chip* regime — the
n agents co-resident on one chip, whose (n, bp) Theta slab fits VMEM
(float32: n <= ~8k at bp=256 against a ~16 MB budget). Past that,
mixing runs through the unbounded-n ``segment_sum``/gather paths in
``repro.core.mixing`` (see ``kernels/ref.py`` for the oracles); an
HBM-resident Theta variant with DMA'd row gathers is the follow-up.

Layout: grid (agent_tiles, feature_tiles). Each grid step's (ba, K) tile
of the neighbour index table is copied into SMEM, so the kernel can issue
data-dependent row gathers from the Theta slab while SMEM holds one tile,
never the whole (R, K) table; Theta streams through the feature dimension
in (n, bp) slabs that stay VMEM-resident across one agent tile, with bp a
multiple of 128 (lane-aligned) and the agent tile a multiple of 8
(sublane-aligned). Weights sit in VMEM as an (ba, K) tile. The ``interpret``
path runs the same program on CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEF_BA = 8  # agents per tile (sublane multiple)
DEF_BP = 256  # feature-tile width (lane multiple)


def _lane(row, k):
    """Lane ``k`` of a (1, K) VMEM row as a (1, 1) value.

    A 1x1 load at a dynamic lane offset is refused by Mosaic (the lane
    index must be provably 128-aligned), so the row is read whole and the
    lane selected with a mask; the sum adds exact zeros, so the value is
    bit-identical to the direct read.
    """
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lanes == k, row, 0.0), axis=1, keepdims=True)


def _sparse_mix_kernel(idx_ref, w_ref, theta_ref, out_ref):
    K = w_ref.shape[1]
    bp = out_ref.shape[1]

    def agent_row(r, _):
        w_row = w_ref[pl.ds(r, 1), :].astype(jnp.float32)  # (1, K)

        def neighbor(k, acc):
            j = idx_ref[r, k]
            contrib = theta_ref[pl.ds(j, 1), :].astype(jnp.float32)
            return acc + _lane(w_row, k) * contrib

        acc = jax.lax.fori_loop(0, K, neighbor, jnp.zeros((1, bp), jnp.float32))
        out_ref[pl.ds(r, 1), :] = acc
        return 0

    jax.lax.fori_loop(0, out_ref.shape[0], agent_row, 0)


def sparse_mix(idx, w, theta, block_a=DEF_BA, block_p=DEF_BP, interpret=False):
    """idx: (R, K) int32 into theta's rows; w: (R, K) float; theta: (n, p).

    Returns (R, p) float32. R == n gives the full neighbour sum; R < n is
    the gathered woken-rows batch.
    """
    n, p = theta.shape
    R, K = idx.shape
    ba = min(block_a, R)
    bp = min(block_p, p)
    nb_a = pl.cdiv(R, ba)
    nb_p = pl.cdiv(p, bp)
    # Pad the row batch to whole agent tiles (index 0, weight 0), so no
    # grid step reads an index row past the table.
    pad = nb_a * ba - R
    idx = jnp.pad(idx.astype(jnp.int32), ((0, pad), (0, 0)))
    w = jnp.pad(w, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _sparse_mix_kernel,
        grid=(nb_a, nb_p),
        in_specs=[
            pl.BlockSpec((ba, K), lambda a, j: (a, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((ba, K), lambda a, j: (a, 0)),
            pl.BlockSpec((n, bp), lambda a, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((ba, bp), lambda a, j: (a, j)),
        out_shape=jax.ShapeDtypeStruct((nb_a * ba, p), jnp.float32),
        interpret=interpret,
    )(idx, w, theta)
    return out[:R]
