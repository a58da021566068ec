"""The part of a request's time in predict that the device does not
account for: each traced request's span, less the time the device was busy
from the span's start to the end of the request's own program (which
counts the wait behind queued training work as device time). What is left
is routing, dispatch, and the transfer of the scores to the host."""

import bisect

from bench import readers


def read(ctx):
    t = ctx.get("trace")
    if t is None or len(t["devices"]) != 1:
        return None
    dev = next(iter(t["devices"].values()))
    runs = sorted((a, b) for name, a, b in dev["modules"] if readers.SCORE_PROGRAM in name)
    starts = [a for a, _ in runs]
    cover = dev["cover"]
    host = []
    for name, _, a, b in t["host_spans"]:
        if name != "bench.predict":
            continue
        i = bisect.bisect_left(starts, a)
        if i == len(runs) or runs[i][0] > b:
            continue
        end = runs[i][1]
        host.append((b - a) - cover.between(a, end))
    return 1e3 * sum(host) * 1e-9 / len(host) if host else None
