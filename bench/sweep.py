"""Offered-load sweep of a serving cell, to find the rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 10 --rates 25 50 100 200

Sets the cell up once, then runs one window per rate, in the order
given, with the cell's own traffic at that rate, and prints one JSON
line per rate: the latency percentiles (from when each request was due),
the share of requests within the mix's ``latency_limit_ms``, the 99th
percentile of each third of the window (a backlog that grows shows as a
rising tail), and the training rate. The benchmark's own runs do not run
this; a mix's fixed rate is set from it, at about four fifths of the
highest rate whose tail stays within the limit without a growing backlog.
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


def sweep(cell, seed: int, seconds: float, rates, devices):
    import numpy as np

    from bench import deploy, harness, traffic

    cfg, mix = cell.cfg, cell.traffic
    dep = deploy.generate(cfg, seed, devices=devices)
    obj, engine, handle = harness.build(cfg, mix, dep, seed, devices)
    res = harness.drive(engine, handle, mix, engine.init_state(
        harness.initial_theta(seed, dep.n, dep.p)))
    warm = set()
    limit_s = float(mix["requests"]["latency_limit_ms"]) * 1e-3
    for rate in rates:
        mix_r = json.loads(json.dumps(mix))
        mix_r["requests"]["rate_per_s"] = float(rate)
        sched = traffic.schedule(mix_r, dep.counts, dep.test_count, seed + int(rate), seconds)
        for c in sorted(set(sched.sizes.tolist()) - warm):
            handle.predict(np.zeros(c, np.int64), np.zeros((c, dep.p), np.float32))
            warm.add(c)
        payloads = [(np.full(c, u, np.int64), dep.features(dep.test_items[u, :c]))
                    for u, c in zip(sched.users.tolist(), sched.sizes.tolist())]
        client = harness.Client(handle, sched, payloads, set(), int(mix["requests"]["senders"]))
        t0 = time.perf_counter()
        a0 = res.wakes_applied
        client.start(t0)
        while time.perf_counter() - t0 < seconds:
            res = harness.drive(engine, handle, mix, res.state)
        wall = time.perf_counter() - t0
        client.join()
        lat = client.latency
        thirds = np.array_split(lat, 3)
        yield {
            "rate_per_s": float(rate),
            "requests": int(lat.size),
            "failed": int(client.failed),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "within_limit": float(np.mean(lat <= limit_s)),
            "p99_by_third_ms": [float(np.percentile(t, 99) * 1e3) for t in thirds],
            "late_p99_ms": float(np.nanpercentile(client.late, 99) * 1e3),
            "drain_s": time.perf_counter() - t0 - wall,
            "updates_per_s": (res.wakes_applied - a0) / wall,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import spec
    from bench.run import NoChip, chips, enable_compile_cache

    cell = spec.resolve(spec.load(ROOT), args.workload, ROOT)
    if not cell.traffic.get("requests"):
        print(f"bench: {args.workload} serves no requests", file=sys.stderr)
        return 2
    enable_compile_cache()
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for row in sweep(cell, args.seed, args.seconds, args.rates, devices):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
