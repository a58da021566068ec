"""The check's control and a fault, read at a cell's own size.

    python3 bench/control.py --workload <cell> --slots <n> --seeds 1 2 3

For each seed: the cell's deployment, the reference's replay of ``--slots``
slots (as many as a run of the cell trains), and the compared numbers of

* ``control`` -- the reference put in the program's place, computed at
  the precision below the configuration's (f32 at full precision -> bf16
  x3 products), its served scores likewise;
* ``half_batch`` -- the reference applying only the first half of each
  slot's woken rows (of each shard's, under the sharded engine).

Under the sharded engine the replays take the placement the engine's own
cut (``repro.sim.partition.partition_graph``, called with the
configuration's options as the engine calls it) makes of the deployment,
so no engine is built; the replays run on the chips the cell asks for.

The sampled requests are those a run of the seed draws, each served at
the last version. A state left unchanged reads ``train_gap`` = 1 by
construction and is not run. Prints one JSON line per seed. The
benchmark's own runs do not run this; it gives the upper readings from
which the limits are chosen.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def readings(cell, seed: int, slots: int, seconds: float = 20.0, devices=None) -> dict:
    import numpy as np

    from bench import deploy, harness, reference, traffic

    cfg, mix = cell.cfg, cell.traffic
    t0 = time.perf_counter()
    dep = deploy.generate(cfg, seed)
    theta0 = harness.initial_theta(seed, dep.n, dep.p)
    prob = reference.wake_probability(traffic.slot_wakes(mix, dep.n), dep.n)
    every = harness.publication_period(mix)
    slots = slots // every * every
    seed31 = deploy.engine_seed(seed)
    placement = engine_placement(cfg, dep, cell.chips)
    placed = dict(placement=placement, shards=cell.chips, devices=devices)

    requests = []
    sched = traffic.schedule(mix, dep.counts, dep.test_count, seed, seconds)
    if sched is not None:
        for i in sorted(harness._sample(sched, int(mix["requests"]["check_sample"]), seed)):
            u = int(sched.users[i])
            requests.append((u, dep.features(dep.test_items[u, : int(dep.test_count[u])])))
    users = harness.sample_users(mix, [u for u, _ in requests])
    pos = {int(u): i for i, u in enumerate(users)}
    serving = bool(mix.get("requests"))

    t1 = time.perf_counter()
    ref, touched, rows_ref = reference.replay(
        dep, cfg, theta0, seed31, prob, slots, every, users, "highest", **placed
    )
    out = {"seed": seed, "slots": slots, "reference_s": time.perf_counter() - t1}

    def compare(name, precision, half):
        theta, _, rows = reference.replay(
            dep, cfg, theta0, seed31, prob, slots, every, users, precision, half, **placed
        )
        score_precision = "exact" if precision == "highest" else precision
        pairs = []
        for u, X in requests:
            row = rows[slots][pos[u]]
            values = reference.scores(np.broadcast_to(row, X.shape), X, score_precision)
            pairs.append((X, values, rows_ref[slots], pos[u]))
        out[name] = harness.numbers(theta0, theta, ref, touched, pairs, cfg["limits"], serving)

    compare("control", "bf16_3x", False)
    compare("half_batch", "highest", True)
    out["total_s"] = time.perf_counter() - t0
    return out


def engine_placement(cfg: dict, dep, chips: int):
    """The (S, R) placement the sharded engine makes of ``dep`` on
    ``chips`` shards, or None where the configuration names the
    single-device engine."""
    import numpy as np

    from bench import harness

    eng = harness.check_engine(cfg, chips)
    if eng["kind"] != "sharded":
        return None
    from repro.sim.partition import partition_graph

    part = partition_graph(dep.graph(), chips, mode=eng.get("partition_mode", "degree"),
                           relabel=eng.get("relabel"))
    return np.array(part.owned)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import spec
    from bench.run import NoChip, chips, enable_compile_cache

    cell = spec.resolve(spec.load(ROOT), args.workload, ROOT)
    enable_compile_cache()
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.slots, devices=devices), default=float),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
