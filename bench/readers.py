"""Shared arithmetic of the per-layer readers in ``bench/metrics/``.

Each reader takes the run's context: ``trace`` (the reduction of
:mod:`bench.trace_reduce`), ``traced`` (applied updates and slots inside
the traced window), ``work`` (the mean work of one applied update,
:mod:`bench.workcount`), ``p`` (the model width), ``device_kind``,
``window`` (host counts of the whole window), ``client`` (per-request
host times), ``serve_counters`` (the serving tier's counters at the
window's ends) and ``placement`` (None for the single-device engine; for
the sharded engine ``shards``, ``exchange_method``, ``rows_per_shard``,
``halo_fraction`` and ``exchange_rows_per_slot``, from which
:func:`bench.workcount.halo_bytes_per_slot` counts the halo's bytes). A
reader that finds nothing to read returns None.
"""

from __future__ import annotations

from bench import peaks

CHUNK_PROGRAM = "_chunk_impl"  # the engine's jitted scan of super-ticks
SCORE_PROGRAM = "_score_rows"  # the serving tier's jitted gather + dot


def _devices(ctx):
    t = ctx.get("trace")
    return None if t is None else list(t["devices"].values())


def program_seconds(dev: dict, program: str) -> float:
    return sum(s for name, s in dev["module_s"].items() if program in name)


def span_ms(ctx, scopes) -> float | None:
    """Device time per super-tick of the ops in ``scopes``, averaged over
    devices: None where no op carries them."""
    devs, slots = _devices(ctx), ctx["traced"]["slots"] if ctx.get("traced") else 0
    if not devs or not slots:
        return None
    per = [sum(d["scope_s"].get(s, 0.0) for s in scopes) for d in devs]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / slots


def supertick_seconds(ctx) -> float | None:
    """Device seconds of the super-tick program, summed over devices."""
    devs = _devices(ctx)
    if not devs:
        return None
    total = sum(program_seconds(d, CHUNK_PROGRAM) for d in devs)
    return total or None


def share_of_least(ctx, flops_key: str, bytes_key: str, seconds: float | None) -> float | None:
    """100 x the least time the chip needs for the applied updates' work
    over the measured device seconds."""
    applied = ctx["traced"]["applied"] if ctx.get("traced") else 0
    if not seconds or not applied:
        return None
    work = ctx["work"]
    least = peaks.least_seconds(
        applied * work[flops_key], applied * work[bytes_key], ctx["device_kind"]
    )
    return 100.0 * least / seconds


def scope_seconds(ctx, scope: str) -> float | None:
    devs = _devices(ctx)
    if not devs:
        return None
    total = sum(d["scope_s"].get(scope, 0.0) for d in devs)
    return total or None
