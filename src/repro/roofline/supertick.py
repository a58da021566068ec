"""Roofline placement of one engine super-tick (PR-6 deliverable).

Lowers an already-built :class:`repro.sim.AsyncEngine` /
:class:`repro.sim.ShardedAsyncEngine` chunk — the exact jitted program
``advance`` runs, compressed halo exchange included —
and pushes the compiled HLO through :func:`repro.roofline.analyze_compiled`
to place the super-tick against the three-term bandwidth roofline:

    bound_s = max(compute_s, memory_s, collective_s) / steps

The ``gap`` row is measured wall-clock per super-tick divided by that
bound: gap ~ 1 means the super-tick runs at the roofline; the remainder
is launch overhead, pipeline bubbles, and unmodelled scalar work. The
MODEL_FLOPs numerator is the *useful* Eq. 4 arithmetic for the expected
wakes per slot (residual + gradient + neighbour mix + axpy), so
``useful_ratio`` exposes padding waste from the static woken-row batch.

The peaks are those of the device the engine runs on, from
:mod:`repro.roofline.peaks`; on a device the table lacks (the CPU among
them) the placement raises instead of borrowing another chip's peaks.
"""

from __future__ import annotations

import numpy as np

from repro.roofline.analysis import Roofline, analyze_compiled
from repro.roofline.peaks import device_peaks


def model_flops_per_supertick(engine) -> float:
    """Useful Eq. 4 FLOPs for the expected wakes of one super-tick.

    Per woken agent: ``2*m*p`` residual matvec + ``2*m*p`` gradient
    reduction + ``2*deg*p`` neighbour mix + ``~8*p`` axpy/regulariser.
    Rate-weighted over agents (an agent's wake probability scales its
    own degree/data contribution), so heterogeneous-rate configs are
    counted correctly.
    """
    probs = np.asarray(engine.wake_probs, dtype=np.float64)
    p = float(engine.p)
    deg = np.zeros_like(probs)
    graph = getattr(engine.update, "graph", None)
    if graph is not None:
        from repro.core.graph import neighbor_counts

        deg = np.asarray(neighbor_counts(graph), dtype=np.float64)
    m = 0.0
    obj = getattr(engine.update, "obj", None)
    data = getattr(obj, "data", None)
    if data is not None and getattr(data, "X", None) is not None:
        m = float(np.asarray(data.X).shape[1])
    per_wake = 4.0 * m * p + 2.0 * deg * p + 8.0 * p
    return float(np.sum(probs * per_wake))


def supertick_roofline(engine, state=None, steps: int = 8) -> Roofline:
    """Compile ``steps`` super-ticks of ``engine`` and analyse the HLO.

    ``state`` defaults to a fresh zero-model ``init_state``; pass a real
    one to analyse mid-run (the program is shape-identical either way).
    Works for both engines: the sharded chunk is lowered with its static
    shard tiles, so the halo collective-permutes / all-gathers land in
    the collective term at their wire dtype (f32/bf16/int8 payloads).
    """
    if state is None:
        state = engine.init_state(np.zeros((engine.n, engine.p)))
    steps = int(steps)
    compiled = engine._chunk.lower(state, engine._static, steps).compile()
    chips = int(getattr(engine, "num_shards", 1))
    model_flops = model_flops_per_supertick(engine) * steps
    roof = analyze_compiled(compiled, chips, model_flops, device_peaks())
    roof.steps = steps
    return roof


def supertick_report(
    engine,
    state=None,
    steps: int = 8,
    measured_s_per_tick: float | None = None,
    prefix: str = "roofline_supertick",
) -> list:
    """CSV-style ``(name, value, note)`` rows for the bench summary.

    Always emits the per-super-tick roofline bound (us) with the
    dominant term; with a measured wall-clock time per super-tick it
    also emits the ``gap`` row (measured / bound — the "remaining gap"
    between the simulator and the bandwidth roofline).
    """
    roof = supertick_roofline(engine, state=state, steps=steps)
    bound_s = max(roof.compute_s, roof.memory_s, roof.collective_s) / max(steps, 1)
    note = (
        f"dominant={roof.dominant} compute={roof.compute_s / steps * 1e6:.3g}us "
        f"memory={roof.memory_s / steps * 1e6:.3g}us "
        f"collective={roof.collective_s / steps * 1e6:.3g}us "
        f"useful_ratio={roof.useful_ratio:.3g} us/slot"
    )
    rows = [(f"{prefix}_bound", bound_s * 1e6, note)]
    if measured_s_per_tick is not None and bound_s > 0:
        gap = measured_s_per_tick / bound_s
        rows.append(
            (
                f"{prefix}_gap",
                gap,
                f"measured {measured_s_per_tick * 1e6:.4g}us / bound "
                f"{bound_s * 1e6:.4g}us ({roof.dominant}-bound); gap = launch "
                "overhead + bubbles + unmodelled scalar work",
            )
        )
    return rows
