"""Run reports: periodic metric drains, JSONL export, and the report CLI.

The drain side of the telemetry layer. The engines accumulate counters
on-device (:mod:`repro.obs.metrics`); ``run(..., metrics_every=N)``
drains them to the host every N slots and appends each drain to a
:class:`RunReport` — the one artifact that holds a run's metadata, its
counter trajectory. Reports round-trip through JSONL (one ``kind``-tagged object per
line, so files stream and append) and merge their rows into the
``BENCH_summary.json`` perf trajectory under ``obs_*`` names.

CLI::

    python -m repro.obs.report results/obs_runreport.jsonl
    python -m repro.obs.report report.jsonl --merge-bench BENCH_summary.json

The first form renders the run summary table (metadata, final counter
totals); ``--merge-bench`` folds the report's ``obs_*`` rows into a
bench summary file (same merge semantics as ``benchmarks/run.py``, which
imports :func:`merge_bench_summary` from here so the two writers cannot
drift).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

from repro.obs.metrics import summarize_counters


def merge_bench_summary(path: str, rows) -> None:
    """Merge ``(name, us_per_call, derived)`` rows into a bench summary.

    The shared writer for the ``name -> {us_per_call, derived}`` map:
    merging (not clobbering) lets partial runs — ``--only`` debug
    passes, subprocess benches, obs reports — update their own entries
    without erasing the accumulated trajectory of everything else.
    """
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    if not isinstance(data, dict):
        data = {}
    data.update({n: {"us_per_call": float(u), "derived": str(d)} for n, u, d in rows})
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)


@dataclasses.dataclass
class RunReport:
    """One run's telemetry: metadata and drained snapshots."""

    meta: dict = dataclasses.field(default_factory=dict)
    snapshots: list = dataclasses.field(default_factory=list)

    def add_snapshot(self, slot: int, counters: dict, derived: dict | None = None):
        """Append one drained metrics snapshot (host-side dict of arrays)."""
        self.snapshots.append(
            {
                "slot": int(slot),
                "counters": summarize_counters(counters),
                "derived": {k: _jsonable(v) for k, v in (derived or {}).items()},
            }
        )

    # -- serialization -----------------------------------------------------
    def to_jsonl(self, path: str) -> None:
        """Write the report as kind-tagged JSONL (meta, then snapshots)."""
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "meta", **self.meta}) + "\n")
            for snap in self.snapshots:
                f.write(json.dumps({"kind": "snapshot", **snap}) + "\n")

    @classmethod
    def from_jsonl(cls, path: str) -> "RunReport":
        """Load a report written by :meth:`to_jsonl`."""
        report = cls()
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                kind = obj.pop("kind", None)
                if kind == "meta":
                    report.meta = obj
                elif kind == "snapshot":
                    report.snapshots.append(obj)
                else:
                    raise ValueError(f"{path}: unknown report line kind {kind!r}")
        return report

    # -- rendering ---------------------------------------------------------
    def bench_rows(self) -> list:
        """The report's contribution to ``BENCH_summary.json``: the final
        snapshot's scalar counters as ``obs_<counter>`` rows, with the
        slot count in the note."""
        rows = []
        if self.snapshots:
            last = self.snapshots[-1]
            for name, value in last["counters"].items():
                if isinstance(value, (int, float)):
                    rows.append(
                        (f"obs_{name}", float(value), f"through slot {last['slot']}")
                    )
        return rows

    def summary_table(self) -> str:
        """Human-readable run summary (the report CLI's default output)."""
        lines = ["== run =="]
        for k, v in sorted(self.meta.items()):
            lines.append(f"  {k:<24} {v}")
        if self.snapshots:
            last = self.snapshots[-1]
            lines.append(f"== counters (slot {last['slot']}, {len(self.snapshots)} drains) ==")
            for k, v in sorted(last["counters"].items()):
                lines.append(f"  {k:<24} {v}")
            for k, v in sorted(last.get("derived", {}).items()):
                lines.append(f"  {k:<24} {v}")
        return "\n".join(lines)


def _jsonable(v):
    import numpy as np

    if isinstance(v, (np.generic, np.ndarray)):
        return np.asarray(v).tolist()
    return v


def main(argv=None) -> int:
    """Entry point for ``python -m repro.obs.report``."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Render a RunReport JSONL and merge its rows into a bench "
        "summary.",
    )
    ap.add_argument("report", help="RunReport JSONL path")
    ap.add_argument(
        "--merge-bench",
        action="append",
        default=[],
        metavar="PATH",
        help="merge the report's obs_* rows into this BENCH_summary.json "
        "(repeatable; keeps the dual-written copies in sync)",
    )
    args = ap.parse_args(argv)
    report = RunReport.from_jsonl(args.report)
    print(report.summary_table())
    rows = report.bench_rows()
    for path in args.merge_bench:
        merge_bench_summary(path, rows)
        print(f"merged {len(rows)} obs rows into {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
