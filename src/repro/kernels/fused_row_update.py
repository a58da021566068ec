"""Pallas TPU kernel: the fused woken-row super-tick update.

One launch covers the whole hot path a woken agent runs per super-tick —
the four separate XLA ops of the unfused engines collapsed into one
VMEM-resident program:

1. **gather**    — read the woken row ``theta[rows[b]]`` out of the slab;
2. **mix**       — its padded neighbour sum ``sum_k w[b,k] theta[idx[b,k]]``
   (the ``sparse_mix`` machinery, row batch B independent of the slab
   height);
3. **row update** — the Eq. 4 / Eq. 6 quadratic-loss step
   ``(1-a) th + a (neigh/d - mu c (grad L + noise))`` with the gradient
   computed in-kernel from the agent's padded data rows
   (``grad L = sum_m mask 2(x.th - y) x / m_hat + 2 lam th``, optional
   per-point L1 clip);
4. **scatter**   — write the replacement row back into the slab; rows
   carrying the sentinel (``rows[b] >= limit``: slot-capacity padding or
   a budget-exhausted DP agent) are skipped, leaving the stale value —
   the engines' ``.at[tgt].set(mode="drop")`` semantics.

Scope mirrors ``sparse_mix``: the on-chip regime where the (nt, pp) slab
fits VMEM (single-device: nt = n; sharded: the (R + Hmax, p) extended
block *after* the halo exchange, which stays a separate collective — the
kernel fuses everything on-chip). The quadratic loss only: the logistic
path keeps the unfused vmap (its exp/log1p inner loop gains nothing from
fusion and the engines gate on ``loss.name``).

Layout: grid over row tiles (bb rows per step). Each step's tile of the
wake-index and neighbour-index tables is copied into SMEM (one tile at
a time, so SMEM never holds the whole (B, K) table) and the kernel
issues data-dependent row gathers from it; the slab streams in once and stays
VMEM-resident; the output slab is initialized from it at step 0 and
updated in place across grid steps (constant out-block index =>
revisited VMEM buffer, one writeback at the end). Feature dim is a
single lane-aligned tile (pp multiple of 128) because the in-kernel
gradient needs whole rows — p past ~512 should stay on the unfused path.
``interpret=True`` runs the same program on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_mix import _lane


DEF_BB = 8  # woken rows per grid step (sublane multiple)


def _fused_row_update_kernel(
    limit,
    clip,
    rows_ref,
    idx_ref,
    w_ref,
    coef_ref,
    X_ref,
    y_ref,
    mask_ref,
    noise_ref,
    theta_ref,
    out_ref,
):
    step = pl.program_id(0)
    bb, K = w_ref.shape
    nt, pp = out_ref.shape
    hi = jax.lax.Precision.HIGHEST

    @pl.when(step == 0)
    def _init_slab():
        # Constant out-block index: this VMEM buffer persists across grid
        # steps, so rows never scattered keep their slab value (drop-mode
        # scatter semantics) and the final writeback emits the full slab.
        out_ref[:, :] = theta_ref[:, :].astype(out_ref.dtype)

    def one_row(r, _):
        row = rows_ref[r, 0]  # caller pads B to a tile multiple with sentinel rows
        grow = jnp.minimum(row, nt - 1)  # sentinel clamps for the gather
        tr = theta_ref[pl.ds(grow, 1), :].astype(jnp.float32)  # (1, pp)
        w_row = w_ref[pl.ds(r, 1), :].astype(jnp.float32)  # (1, K)

        def neighbor(k, acc):
            j = idx_ref[r, k]
            contrib = theta_ref[pl.ds(j, 1), :].astype(jnp.float32)
            return acc + _lane(w_row, k) * contrib

        neigh = jax.lax.fori_loop(0, K, neighbor, jnp.zeros((1, pp), jnp.float32))

        Xr = X_ref[r].astype(jnp.float32)  # (m, pp)
        yr = y_ref[pl.ds(r, 1), :].astype(jnp.float32)  # (1, m)
        mr = mask_ref[pl.ds(r, 1), :].astype(jnp.float32)  # (1, m)
        # Per-point residuals 2 (x.th - y) — the quadratic point grad is
        # resid * x, so the clip/mask/mean pipeline stays rank-2 (1, m).
        dots = jax.lax.dot_general(
            tr, Xr, (((1,), (1,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32,
        )  # (1, m)
        resid = 2.0 * (dots - yr)
        if clip is not None:
            # L1 clip per point: |g|_1 = |resid| * sum_p |x_p|.
            abs_x = jax.lax.dot_general(
                jnp.ones((1, pp), jnp.float32),
                jnp.abs(Xr),
                (((1,), (1,)), ((), ())),
                precision=hi,
                preferred_element_type=jnp.float32,
            )  # (1, m)
            norms = jnp.abs(resid) * abs_x
            resid = resid * jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12))
        m_hat = jnp.maximum(jnp.sum(mr), 1.0)
        g_sum = jax.lax.dot_general(
            resid * mr, Xr, (((1,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32,
        )  # (1, pp)

        alpha = coef_ref[pl.ds(r, 1), pl.ds(0, 1)]  # (1, 1) broadcasts below
        deg = coef_ref[pl.ds(r, 1), pl.ds(1, 1)]
        cmu = coef_ref[pl.ds(r, 1), pl.ds(2, 1)]
        lam2 = coef_ref[pl.ds(r, 1), pl.ds(3, 1)]
        grads = g_sum / m_hat + lam2 * tr + noise_ref[pl.ds(r, 1), :].astype(jnp.float32)
        new = (1.0 - alpha) * tr + alpha * (neigh / deg - cmu * grads)

        @pl.when(row < limit)
        def _scatter():
            out_ref[pl.ds(grow, 1), :] = new.astype(out_ref.dtype)

        return 0

    jax.lax.fori_loop(0, bb, one_row, 0)


def fused_row_update(
    rows,
    idx,
    w,
    coef,
    X,
    y,
    mask,
    noise,
    theta,
    limit,
    clip=None,
    block_b=DEF_BB,
    interpret=False,
):
    """Fused gather + mix + Eq. 4 row update + scatter over a theta slab.

    ``rows``: (B,) int32 slab rows to update; entries ``>= limit`` are
    sentinels (computed but never scattered). ``idx``/``w``: (B, K)
    padded neighbour tables *already row-gathered* to the woken batch
    (indices address the slab, which may be halo-extended). ``coef``:
    (B, 4+) f32 per-row ``[alpha, deg, mu*conf, 2*lam]`` (extra columns
    ignored). ``X``: (B, m, p), ``y``/``mask``: (B, m) padded data rows;
    ``noise``: (B, p) gradient perturbation (zeros = non-private).
    ``theta``: (nt, p) slab. Returns the (nt, p) f32 updated slab.

    Caller contract (``repro.kernels.ops`` handles both): p is one
    lane-aligned feature tile, and B is a multiple of ``block_b`` with
    sentinel padding rows.
    """
    nt, p = theta.shape
    B, K = idx.shape
    bb = min(block_b, B)
    nb = pl.cdiv(B, bb)
    m = X.shape[1]
    smem = pltpu.SMEM
    kernel = functools.partial(
        _fused_row_update_kernel, limit, None if clip is None else float(clip)
    )
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, 1), lambda b: (b, 0), memory_space=smem),
            pl.BlockSpec((bb, K), lambda b: (b, 0), memory_space=smem),
            pl.BlockSpec((bb, K), lambda b: (b, 0)),
            pl.BlockSpec((bb, coef.shape[1]), lambda b: (b, 0)),
            pl.BlockSpec((bb, m, p), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, m), lambda b: (b, 0)),
            pl.BlockSpec((bb, m), lambda b: (b, 0)),
            pl.BlockSpec((bb, p), lambda b: (b, 0)),
            pl.BlockSpec((nt, p), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nt, p), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nt, p), jnp.float32),
        interpret=interpret,
    )(rows.astype(jnp.int32)[:, None], idx.astype(jnp.int32), w, coef, X, y, mask, noise, theta)
