"""The one general load generator: reads a traffic mix's parameters and
turns them, with the run's seed, into a fixed request schedule.

A mix (``bench/traffic/<name>.json``) states the engine's clock and slot
sizes and, where it serves, its request stream:

* ``arrivals: "poisson"`` — an open loop at ``rate_per_s``. The count in
  a window of s seconds is fixed at ``round(rate * s)`` and the arrival
  times are that many sorted uniform draws: the law of a Poisson process
  given its count.
* ``user_weight: "rating_count"`` — a request is for one user, drawn with
  probability proportional to the user's number of ratings.

Every seed offers the same work in another order: the gaps between
arrivals and the rating counts of the requested users are drawn once,
from a fixed stream; the run's seed shuffles the gaps, shuffles which
request comes when, and picks among the users of each drawn count (every
seed's population holds the same counts, relabelled).
* ``rows: "held_out"`` — it asks for the scores of that user's held-out
  items, one row per item;
* ``senders`` — how many client threads send them, each taking the next
  request in order of due time, so up to that many are in flight.
"""

from __future__ import annotations

import dataclasses

import numpy as np

KNOWN = {"arrivals": ("poisson",), "user_weight": ("rating_count",), "rows": ("held_out",)}


@dataclasses.dataclass
class Schedule:
    """The requests of one window, in order of their due times."""

    due_s: np.ndarray  # (R,) seconds after the window opens
    users: np.ndarray  # (R,) int64
    sizes: np.ndarray  # (R,) rows per request

    def __len__(self) -> int:
        return int(self.due_s.size)


def check(traffic: dict) -> None:
    """Refuse a mix this generator does not implement."""
    req = traffic.get("requests")
    if req is None:
        return
    for key, allowed in KNOWN.items():
        if req.get(key) not in allowed:
            raise ValueError(f"traffic requests.{key}={req.get(key)!r}: known {allowed}")
    if not req.get("rate_per_s", 0) > 0:
        raise ValueError("traffic requests.rate_per_s must be positive")
    if not int(req.get("senders", 0)) >= 1:
        raise ValueError("traffic requests.senders must be at least 1")


def schedule(traffic: dict, counts: np.ndarray, test_count: np.ndarray, seed: int,
             seconds: float) -> Schedule | None:
    """The request schedule of a window of ``seconds``, from ``seed``."""
    req = traffic.get("requests")
    if req is None:
        return None
    fixed = np.random.default_rng(11)
    total = int(round(float(req["rate_per_s"]) * seconds))
    gaps = np.diff(np.sort(fixed.uniform(0.0, seconds, size=total)), prepend=0.0)
    # Users in order of rating count: position k holds the same count
    # whatever the seed; users of equal count are in the seed's order.
    order = np.argsort(np.asarray(counts), kind="stable")
    w = np.asarray(counts, np.float64)[order]
    ranks = fixed.choice(w.size, size=total, p=w / w.sum())
    rng = np.random.default_rng([int(seed), 11])
    due = np.cumsum(rng.permutation(gaps))
    users = order[rng.permutation(ranks)].astype(np.int64)
    return Schedule(due_s=due, users=users, sizes=np.asarray(test_count)[users])


def slot_wakes(traffic: dict, n: int) -> float:
    """Expected wakes per slot: the mix's share of the population."""
    return float(traffic["slot_wakes_per_agent"]) * n
