"""Multi-device sharded async engine walkthrough.

Shards a 20,000-agent random geometric collaboration graph across 4
devices: the chips of a four-chip TPU host, or under ``JAX_PLATFORMS=cpu``
4 XLA host-platform devices (the same ``shard_map`` program runs on both): a reverse Cuthill–McKee relabel pass co-locates
graph neighbours so the cut shrinks, agent blocks carry their own slice
of the dataset (no replicated ``obj.data``), and the halo exchange goes
point-to-point — each shard ships only the border rows its neighbour
shards actually read. One :class:`repro.sim.EngineConfig` drives both
engines through :func:`repro.sim.make_engine`; the wire format is an
:class:`repro.sim.ExchangeSpec` (here also demonstrated with bf16
payloads + error feedback, which halves the interconnect bytes).
Cross-checks the result against the single-device batched engine — under
forced wake sets the two are bit-identical; under sampled clocks both
land on the same fixed point.

Run:  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/sharded_async_simulation.py
      JAX_PLATFORMS=cpu PYTHONPATH=src python examples/sharded_async_simulation.py --smoke   # CI-sized

Crash-safe resume (the CI checkpoint lane drives exactly this pair)::

    # write rotating checkpoints, then die mid-run (exit code 7)
    python examples/sharded_async_simulation.py --smoke \
        --checkpoint-dir ckpts --checkpoint-every 4 --kill-after 8
    # pick up from the newest valid entry and finish (parity assert included)
    python examples/sharded_async_simulation.py --smoke \
        --checkpoint-dir ckpts --checkpoint-every 4 --resume
"""

import argparse
import os

from repro.launch.runtime import force_host_devices

# Before JAX starts: a JAX_PLATFORMS=cpu run splits the host into 4
# devices; a four-chip host runs the shards on its chips.
force_host_devices(4)

import numpy as np  # noqa: E402

from repro.core import AgentData, make_objective, random_geometric_graph  # noqa: E402
from repro.sim import (  # noqa: E402
    CDUpdate,
    ChurnConfig,
    EngineConfig,
    ExchangeSpec,
    Scenario,
    make_engine,
    partition_graph,
)


def main(smoke: bool = False, checkpoint_dir=None, checkpoint_every=0,
         keep_last=3, resume=False, kill_after=0):
    import jax

    from repro.checkpoint import restore, save_engine_checkpoint

    rng = np.random.default_rng(0)
    n, p, m, shards = (2_000, 4, 8, 4) if smoke else (20_000, 8, 16, 4)
    slots, record_every = (12, 6) if smoke else (40, 10)
    graph = random_geometric_graph(n, rng, avg_degree=16.0)
    targets = rng.normal(size=(n, p)) / np.sqrt(p)
    X = rng.normal(size=(n, m, p)) / np.sqrt(p)
    y = np.einsum("nmp,np->nm", X, targets)
    data = AgentData(X=X, y=y, mask=np.ones((n, m)))
    obj = make_objective(graph, data, "quadratic", mu=0.5, mix_mode="sparse")
    Theta0 = np.zeros((n, p))
    update = CDUpdate(obj)

    print(f"devices: {len(jax.devices())}, shards: {shards}")
    # One config, both engines. Placement fields (relabel, exchange) are
    # no-ops on the single-device side, so the parity pair shares it.
    cfg = EngineConfig(
        slot_wakes=n / 20.0,
        seed=1,
        relabel="rcm",
        exchange=ExchangeSpec(method="auto"),
        scenario=Scenario(churn=ChurnConfig(leave_prob=0.005, rejoin_prob=0.2)),
    )
    # Locality matters: agent ids carry no spatial information, so plain
    # contiguous blocks read mostly remote rows; the RCM relabel shrinks
    # the cut by an order of magnitude and unlocks the p2p exchange.
    base = partition_graph(graph, shards)
    eng = make_engine(update, cfg, shards=shards)
    part = eng.part
    print(
        f"partition: mode={part.mode} rows/shard<={part.rows_per_shard} "
        f"tile K={part.tile_width}"
    )
    print(
        f"halo fraction: {base.halo_fraction():.2f} (no relabel) -> "
        f"{part.halo_fraction():.2f} (RCM); exchange={eng.exchange_method}, "
        f"{part.exchange_rows(eng.exchange_method)} rows/super-tick vs "
        f"{base.exchange_rows('all_gather')} unrelabeled all_gather"
    )

    if kill_after > 0:
        # CI crash rehearsal: checkpoint every few slots, then die hard
        # mid-run (no atexit, no cleanup — exactly like a preempted node).
        assert checkpoint_dir is not None and checkpoint_every > 0
        eng.run(Theta0, slots=min(kill_after, slots),
                checkpoint_every=checkpoint_every, checkpoint_dir=checkpoint_dir,
                checkpoint_keep_last=keep_last)
        print(f"[kill]     checkpointed through slot {min(kill_after, slots)}, dying now")
        os._exit(7)

    state0, start = None, 0
    if resume:
        state0, start = restore(eng, checkpoint_dir)
        print(f"[resume]   picked up slot {start} from {checkpoint_dir}")
    res = eng.run(
        Theta0,
        slots=slots - start,
        record_every=record_every,
        state=state0,
        checkpoint_every=checkpoint_every,
        checkpoint_dir=checkpoint_dir if checkpoint_every > 0 else None,
        checkpoint_keep_last=keep_last,
    )
    print("[sharded]  Q:", " -> ".join(f"{q:.1f}" for q in res.objective))
    print(
        f"           {res.wakes_applied} wakes over {res.slots} super-ticks, "
        f"{res.messages:.0f} p-vectors broadcast, "
        f"{int((~res.active).sum())} agents currently departed"
    )

    # Compressed halos: ship the border rows as bf16 with an error-feedback
    # accumulator — half the interconnect bytes, same fixed point (the EF
    # loop re-injects each slot's quantization residual next slot).
    wire = ExchangeSpec(method="p2p", dtype="bf16", error_feedback=True)
    ceng = make_engine(update, cfg, shards=shards, exchange=wire)
    cres = ceng.run(Theta0, slots=slots)
    drift = float(np.abs(cres.Theta - res.Theta).max())
    f32_bytes = part.exchange_rows("p2p") * ExchangeSpec().payload_bytes_per_row(p)
    bf16_bytes = part.exchange_rows("p2p") * wire.payload_bytes_per_row(p)
    print(
        f"[bf16+ef]  halo payload {f32_bytes} -> {bf16_bytes} bytes/super-tick "
        f"({f32_bytes / bf16_bytes:.1f}x less wire), |Theta - f32 Theta| "
        f"<= {drift:.1e}"
    )

    # Forced wake sets: the sharded program IS the single-device engine,
    # under any relabeling and either exchange method.
    single = make_engine(update, cfg, slot_wakes=64.0)
    s1 = single.init_state(Theta0)
    sS = eng.init_state(Theta0)
    mask_rng = np.random.default_rng(7)
    for _ in range(3):
        mask = mask_rng.random(n) < 0.005
        s1 = single.step(s1, mask)
        sS = eng.step(sS, mask)
    exact = np.array_equal(np.asarray(s1.Theta), eng.global_theta(sS))
    print(f"[parity]   forced wake sets bit-identical to AsyncEngine: {exact}")
    # CI runs this example as a check: a broken parity must fail the lane,
    # not just print False.
    assert exact, "sharded engine diverged from AsyncEngine under forced wakes"


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true", help="CI-sized problem")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="slots between rotating engine checkpoints (0 = off)")
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid entry and finish the run")
    ap.add_argument("--kill-after", type=int, default=0,
                    help="checkpoint through this many slots then os._exit(7)")
    a = ap.parse_args()
    main(smoke=a.smoke, checkpoint_dir=a.checkpoint_dir,
         checkpoint_every=a.checkpoint_every, keep_last=a.keep_last,
         resume=a.resume, kill_after=a.kill_after)
