"""End-to-end P2P-DP training driver.

Runs the paper's technique on a real model end-to-end on whatever devices
exist (CPU here, TPU mesh in production): personal models per agent,
per-round DP perturbation, ppermute/dense gossip, periodic checkpointing
and eval. This is the driver behind examples/decentralized_lm.py.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --preset tiny --steps 50 --batch 4 --seq 128
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.configs.base import P2PConfig
from repro.core import spmd
from repro.data.synthetic import token_stream
from repro.launch.mesh import make_mesh, use_mesh
from repro.launch.runtime import enable_compile_cache
from repro.models import build_model
from repro.models.encdec import enc_len


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "small", "full"],
                    help="tiny/small = reduced configs for CPU; full = assigned config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4, help="per-agent batch")
    ap.add_argument("--seq", type=int, default=129)
    ap.add_argument("--agents", type=int, default=None, help="default: data-axis size")
    ap.add_argument("--mesh", default="1x1", help="e.g. 4x2 (data x model)")
    ap.add_argument("--mu", type=float, default=0.5)
    ap.add_argument("--alpha", type=float, default=0.9)
    ap.add_argument("--eps", type=float, default=0.0, help="DP budget; 0 = off")
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--gossip", default="ppermute", choices=["ppermute", "dense"])
    ap.add_argument("--no-p2p", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint rotation depth (newest K entries kept)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args):
    if args.preset == "full":
        cfg = get_config(args.arch)
    elif args.preset == "small":
        cfg = get_reduced(args.arch, num_layers=2, d_model=256, d_ff=512,
                          vocab_size=2048, dtype="float32")
    else:
        cfg = get_reduced(args.arch, dtype="float32")
    return cfg


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    dshape = tuple(int(x) for x in args.mesh.split("x"))
    n_dev = len(jax.devices())
    assert np.prod(dshape) <= n_dev, f"mesh {dshape} needs more than {n_dev} devices"
    mesh = make_mesh(dshape, ("data", "model"))
    cfg = build(args)
    bundle = build_model(cfg, remat=False)
    A = args.agents or mesh.shape["data"]

    p2p = P2PConfig(
        agent_mode="full",
        enabled=not args.no_p2p,
        dp_enabled=args.eps > 0,
        eps_bar=args.eps if args.eps > 0 else 1.0,
        planned_rounds=args.steps,
        clip=args.clip,
        mu=args.mu,
        neighbor_offsets=(1,) if A <= 4 else (1, 2),
        gossip_dtype=None,
    )

    key = jax.random.PRNGKey(args.seed)
    params = jax.vmap(bundle.init)(jax.random.split(key, A))
    start_step = 0
    if args.resume and args.checkpoint_dir:
        try:
            params, start_step, _ = load_checkpoint(args.checkpoint_dir, params)
            print(f"resumed from step {start_step}")
        except FileNotFoundError:
            pass

    with use_mesh(mesh):
        step_fn, eps_step, noise_scale = spmd.make_train_step(
            bundle, p2p, mesh, args.batch, alpha=args.alpha, gossip=args.gossip
        )
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

        # Heterogeneous per-agent token streams (personalization signal).
        stream = token_stream(cfg.vocab_size, A * args.batch, args.seq, args.seed, A)
        t0 = time.time()
        history = []
        for step in range(start_step, args.steps):
            toks = next(stream).reshape(A, args.batch, args.seq)
            batch = {"tokens": jnp.asarray(toks)}
            if cfg.is_encdec:
                batch["embeds"] = jax.random.normal(
                    jax.random.fold_in(key, step),
                    (A, args.batch, enc_len(args.seq), cfg.d_model),
                    jnp.float32,
                )
            params, metrics = step_fn(params, batch, jax.random.fold_in(key, 10_000 + step))
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                row = {"step": step, "loss": round(loss, 4),
                       "grad_norm": round(float(metrics["grad_norm"]), 3),
                       "elapsed_s": round(time.time() - t0, 1)}
                history.append(row)
                print(json.dumps(row), flush=True)
            if args.checkpoint_dir and (step + 1) % args.checkpoint_every == 0:
                save_checkpoint(args.checkpoint_dir, params, step=step + 1,
                                extra={"eps_step": eps_step},
                                keep_last=args.keep_last)
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, params, step=args.steps,
                        extra={"eps_step": eps_step, "noise_scale": noise_scale},
                        keep_last=args.keep_last)
    if args.eps > 0:
        from repro.core.privacy import compose_kairouz

        spent = compose_kairouz(np.full(args.steps - start_step, eps_step), p2p.delta_bar)
        print(f"DP: per-step eps={eps_step:.4f}, composed eps over run={spent:.3f} "
              f"(budget {p2p.eps_bar})")
    return history


if __name__ == "__main__":
    main()
