"""Kernel micro-bench: Pallas (interpret on CPU; compiled on TPU) vs the
pure-jnp oracle. On this CPU container the numbers characterize the oracle
path (the Pallas timings are interpret-mode and not meaningful as TPU perf);
the bench exists so the same harness runs on real hardware unchanged.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref


def _time(fn, *args, iters=5):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def run(verbose=True):
    rng = np.random.default_rng(0)
    rows = []

    grads = jnp.asarray(rng.normal(size=(256, 4096)), jnp.float32)
    noise = jnp.asarray(rng.laplace(size=(4096,)), jnp.float32)
    us_ref = _time(jax.jit(lambda g, n: ref.dp_clip_noise_ref(g, n, 1.0, 0.1)), grads, noise)
    rows.append(("dp_clip_noise_ref_256x4096", us_ref, "oracle jnp"))

    mix = jnp.asarray(rng.random((64, 64)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=(64, 8192)), jnp.float32)
    us_ref = _time(jax.jit(ref.graph_mix_ref), mix, theta)
    rows.append(("graph_mix_ref_64x8192", us_ref, "oracle jnp"))

    G, Q, N, Pd = 8, 128, 64, 64
    C = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(G, Q, N)), jnp.float32)
    cum = jnp.asarray(np.cumsum(-np.abs(rng.normal(size=(G, Q)) * 0.1), 1), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(G, Q))), jnp.float32)
    x = jnp.asarray(rng.normal(size=(G, Q, Pd)), jnp.float32)
    us_ref = _time(jax.jit(ref.ssm_chunk_ref), C, B, cum, dt, x)
    rows.append(("ssm_chunk_ref_8x128", us_ref, "oracle jnp"))

    if verbose:
        for name, us, note in rows:
            print(f"{name},{us:.1f},{note}")
    return rows


if __name__ == "__main__":
    run()
