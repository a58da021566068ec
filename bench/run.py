"""Benchmark entry point: one run of one cell, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, generates its deployment on
the device from the seed, builds the system, warms up every shape the
cell uses, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output (``--trace 0``: the cell's end-to-end
metrics; ``--trace 1``: its per-layer metrics from a profiled part of
the window). The numbers compared and their limits close standard error.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(count: int):
    """The first ``count`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} TPUs, JAX found {len(devices)}")
    return devices[:count]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed place in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` puts it. Every program is kept,
    also the small ones (each request size's serving program), so a second
    run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metrics_of(cell, out: dict, trace: bool) -> dict:
    """The result's ``metrics``: end-to-end values, or per-layer readings."""
    from bench import spec

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["values"][m["name"]], "unit": m["unit"]}
        return metrics
    for m in cell.per_layer:
        value = spec.reader(m["name"])(out["ctx"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: also write the trace's compact events here (gzip JSON)")
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.resolve(spec.load(ROOT), args.workload, ROOT)
    enable_compile_cache()
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices, _T_START,
                      keep_trace=args.keep_trace)
    result = {
        "correct": out["correct"],
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics_of(cell, out, bool(args.trace)),
        "device": out["device"],
    }
    if args.trace:
        result["breakdown"] = out["breakdown"]
    result["window"] = out["window"]
    result["checks"] = out["checks"]
    for err in out["errors"]:
        print(err, file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
