import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run (deliverable e).

Lowers + compiles the real train/prefill/serve step for every
(architecture x input shape) on the production mesh — 16x16 single-pod and
2x16x16 multi-pod — using ShapeDtypeStruct inputs (no allocation), then
prints memory_analysis / cost_analysis and derives the roofline terms
(deliverable g).

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import AGENT_MODES, ARCH_IDS, SHAPES, get_config
from repro.configs.base import P2PConfig
from repro.core import spmd
from repro.launch.mesh import make_production_mesh, use_mesh
from repro.models import build_model
from repro.models.encdec import enc_len
from repro.models.sharding import batch_specs, cache_specs, param_specs
from repro.roofline.analysis import analyze_compiled
from repro.roofline.peaks import peaks_for

SLIDING_WINDOW_500K = 8192


def arch_config_for_shape(arch: str, shape_name: str):
    """Resolve the model config, applying the long-context attention policy."""
    cfg = get_config(arch)
    if shape_name == "long_500k" and cfg.family != "ssm" and cfg.sliding_window is None:
        # sub-quadratic requirement: windowed attention for attention archs
        # (SSM/hybrid state paths are already O(1); zamba2's shared attention
        # block gets the same ring-buffer window).
        cfg = dataclasses.replace(cfg, sliding_window=SLIDING_WINDOW_500K)
    return cfg


def input_specs(arch: str, shape_name: str, mesh, gossip="ppermute",
                p2p_on=True, dp_on=True, cfg_overrides=None, moe_overrides=None,
                remat=True):
    """ShapeDtypeStruct stand-ins + shardings for one (arch, shape) combo.

    Returns (step_fn, example_args (SDS), in_shardings, out_shardings, meta).
    """
    cfg = arch_config_for_shape(arch, shape_name)
    if moe_overrides and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe_overrides))
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    bundle = build_model(cfg, remat=remat)
    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    if shape.kind == "train":
        agent_mode = AGENT_MODES[arch]
        A = spmd.num_agents(mesh, agent_mode)
        assert shape.global_batch % A == 0, (arch, shape_name, A)
        per_agent = shape.global_batch // A
        p2p = P2PConfig(
            agent_mode=agent_mode, enabled=p2p_on, dp_enabled=dp_on,
            planned_rounds=100,
        )
        step, eps_step, noise_scale = spmd.make_train_step(
            bundle, p2p, mesh, per_agent, gossip=gossip
        )
        params = jax.eval_shape(
            jax.vmap(bundle.init), jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), A))
        )
        pspecs = param_specs(params, mesh, agent_mode, A)
        batch = {"tokens": jax.ShapeDtypeStruct((A, per_agent, shape.seq_len + 1), jnp.int32)}
        if cfg.is_encdec:
            batch["embeds"] = jax.ShapeDtypeStruct(
                (A, per_agent, enc_len(shape.seq_len), cfg.d_model), jnp.float32
            )
        bspecs = batch_specs(batch, mesh, agent_mode)
        shardify = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
        )
        in_sh = (shardify(pspecs), shardify(bspecs), NamedSharding(mesh, P()))
        out_sh = (shardify(pspecs), None)
        args = (params, batch, key_sds)
        # tokens per round across all agents:
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * cfg.active_param_count() * tokens
        meta = dict(agent_mode=agent_mode, n_agents=A, eps_step=eps_step,
                    noise_scale=noise_scale, model_flops=model_flops,
                    donate=(0,))
        return step, args, in_sh, out_sh, meta

    # ---- inference shapes (serve): single shared model, FSDP+TP ----------
    params = jax.eval_shape(bundle.init, key_sds)
    pspecs = param_specs(params, mesh, "serve", 1)
    shardify = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )

    if shape.kind == "prefill":

        def prefill_step(params, batch):
            return bundle.prefill(params, batch)

        batch = {"tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32)}
        if cfg.is_encdec:
            batch["embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, enc_len(shape.seq_len), cfg.d_model), jnp.float32
            )
        bspecs = batch_specs(batch, mesh, "serve")
        in_sh = (shardify(pspecs), shardify(bspecs))
        # constrain outputs: logits batch-sharded (+vocab over model), caches
        # via cache_specs — leaving them open lets GSPMD replicate the whole
        # prefill loop carry.
        out_shapes = jax.eval_shape(prefill_step, params, batch)
        lead = ("pod", "data") if "pod" in mesh.shape else "data"

        def out_spec(leaf):
            spec = [None] * len(leaf.shape)
            if len(leaf.shape) == 3 and leaf.shape[-1] == cfg.padded_vocab:
                spec[0] = lead
                if cfg.padded_vocab % mesh.shape["model"] == 0:
                    spec[-1] = "model"
                return P(*spec)
            return None  # resolved below for caches

        logits_spec = out_spec(out_shapes[0])
        cache_sp = cache_specs(out_shapes[1], mesh, batch_sharded=True) if (
            isinstance(out_shapes, tuple) and len(out_shapes) > 1 and out_shapes[1] is not None
        ) else None
        out_sh = (
            NamedSharding(mesh, logits_spec) if logits_spec else None,
            shardify(cache_sp) if cache_sp is not None else None,
        )
        args = (params, batch)
        model_flops = 2.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len
        meta = dict(agent_mode="serve", model_flops=model_flops, donate=())
        return prefill_step, args, in_sh, out_sh, meta

    # decode
    def serve_step(params, token, caches, pos):
        return bundle.decode(params, token, caches, pos)

    caches = jax.eval_shape(lambda: bundle.init_cache(None, shape.global_batch, shape.seq_len))
    cspecs = cache_specs(caches, mesh, batch_sharded=True)
    token = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = batch_specs({"t": token}, mesh, "serve")["t"]
    in_sh = (
        shardify(pspecs),
        NamedSharding(mesh, tok_spec),
        shardify(cspecs),
        NamedSharding(mesh, P()),
    )
    out_sh = (None, shardify(cspecs))
    args = (params, token, caches, pos)
    model_flops = 2.0 * cfg.active_param_count() * shape.global_batch
    meta = dict(agent_mode="serve", model_flops=model_flops, donate=(2,))
    return serve_step, args, in_sh, out_sh, meta


def run_one(arch: str, shape_name: str, multi_pod: bool, gossip="ppermute",
            p2p_on=True, dp_on=True, verbose=True, seq_parallel=False,
            cfg_overrides=None, moe_overrides=None, variant="", remat=True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    # Inference paths anchor activation shardings at the batch dim (GSPMD
    # otherwise replicates unconstrained loop carries). Train steps get their
    # sharding from the agent-stacked params/batch, so axes stay unset there.
    from repro.models.sharding import set_activation_axes, set_seq_axis

    if SHAPES[shape_name].kind != "train":
        set_activation_axes(("pod", "data") if multi_pod else "data")
    else:
        set_activation_axes(None)
    set_seq_axis("model" if seq_parallel else None)
    try:
        step, args, in_sh, out_sh, meta = input_specs(
            arch, shape_name, mesh, gossip=gossip, p2p_on=p2p_on, dp_on=dp_on,
            cfg_overrides=cfg_overrides, moe_overrides=moe_overrides, remat=remat,
        )
        with use_mesh(mesh):
            jitted = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=meta.get("donate", ()),
            )
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    finally:
        set_activation_axes(None)
        set_seq_axis(None)
    mem = compiled.memory_analysis()
    # The dry run places the program on its launch target, a v5e pod.
    roof = analyze_compiled(compiled, chips, meta["model_flops"], peaks_for("TPU v5 lite"))
    mem_row = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        try:
            mem_row[k] = int(getattr(mem, k))
        except Exception:
            pass
    row = {
        "arch": arch,
        "shape": shape_name,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "gossip": gossip,
        "p2p": p2p_on,
        "dp": dp_on,
        "agent_mode": meta["agent_mode"],
        "n_agents": meta.get("n_agents"),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_row,
        **roof.row(),
        "collective_ops": roof.collectives.get("_counts"),
        "collective_breakdown": {k: v for k, v in roof.collectives.items() if not k.startswith("_")},
    }
    if verbose:
        print(f"== {arch} x {shape_name} on {row['mesh']} ({meta['agent_mode']}) ==")
        print("memory_analysis:", mem)
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print("cost_analysis: flops=%.3e bytes=%.3e" % (
            float(ca.get("flops", 0)), float(ca.get("bytes accessed", 0))))
        print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs dominant=%s useful=%.2f" % (
            roof.compute_s, roof.memory_s, roof.collective_s, roof.dominant, roof.useful_ratio))
        sys.stdout.flush()
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all arch x shape combos")
    ap.add_argument("--gossip", default="ppermute", choices=["ppermute", "dense"])
    ap.add_argument("--no-p2p", action="store_true")
    ap.add_argument("--no-dp", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    done = set()
    if args.out and args.skip_existing:
        try:
            with open(args.out) as f:
                for line in f:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"], r["gossip"]))
        except FileNotFoundError:
            pass

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                if (arch, shape_name, mesh_name, args.gossip) in done:
                    print(f"skip {arch} x {shape_name} on {mesh_name} (done)")
                    continue
                try:
                    row = run_one(
                        arch, shape_name, mp, gossip=args.gossip,
                        p2p_on=not args.no_p2p, dp_on=not args.no_dp,
                    )
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(row) + "\n")
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print("dry-run: all combinations lowered and compiled OK")


if __name__ == "__main__":
    main()
