"""The work an applied update needs, counted from the deployment's sizes.

One Eq. 4 update of agent i (quadratic loss, per-point L1 clip) reads the
agent's own m_i training points (x: p floats, y: 1 float each), the rows
of its |N_i| neighbours, and its own row, and writes its own row back.
Counted in f32 (4 bytes) over the agent's *own* sizes: padding to the
largest dataset or the largest degree, lane padding, and whichever path
(fused or not) the program takes count nothing. A program that stops
paying for padding raises its share; it does not change this count.

FLOPs per training point: the dot x.theta (2p), the residual and its
factor 2 (2), the point gradient r x (p), its L1 norm (2p), the clip
scale (p) and the accumulation (p): 7p + 2. Per neighbour a
multiply-add per feature (2p). The row step (regulariser, mix with
the old row, divide by the degree): 8p.
"""

from __future__ import annotations

import numpy as np

F32 = 4


def per_update(m: np.ndarray, deg: np.ndarray, p: int) -> dict:
    """Bytes and FLOPs of one update of each agent, split by layer.

    ``m``: (n,) training points per agent; ``deg``: (n,) neighbours per
    agent. Returns (n,) arrays: ``mix_bytes``/``mix_flops`` (the
    neighbour sum), ``row_bytes``/``row_flops`` (the local gradient and
    row step: own data and own row read), ``write_bytes`` (own row
    written back), and the totals ``bytes``/``flops``.
    """
    m = np.asarray(m, np.float64)
    deg = np.asarray(deg, np.float64)
    mix_bytes = F32 * deg * p
    mix_flops = 2.0 * deg * p
    row_bytes = F32 * (m * p + m + p)
    row_flops = m * (7.0 * p + 2.0) + 8.0 * p
    write_bytes = np.full_like(m, F32 * p)
    return {
        "mix_bytes": mix_bytes,
        "mix_flops": mix_flops,
        "row_bytes": row_bytes,
        "row_flops": row_flops,
        "write_bytes": write_bytes,
        "bytes": mix_bytes + row_bytes + write_bytes,
        "flops": mix_flops + row_flops,
    }


def halo_bytes_per_slot(placement: dict, p: int) -> int:
    """Bytes the sharded engine's halo exchange ships in one slot, summed
    over shards: ``exchange_rows_per_slot`` rows of p f32 each, from the
    placement facts the harness reads off the engine. Padding rows count,
    because the static shapes ship them."""
    return int(placement["exchange_rows_per_slot"]) * int(p) * F32


def rate_weighted_mean(work: dict, rates: np.ndarray) -> dict:
    """Expected work of one applied update: agents wake in proportion to
    their clock rates, so each agent's work is weighted by its rate."""
    w = np.asarray(rates, np.float64)
    w = w / w.sum()
    return {k: float(np.dot(w, v)) for k, v in work.items()}
