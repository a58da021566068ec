"""Device time of one run of the serving program (gather + dot)."""

from bench import readers


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    runs = [b - a for d in t["devices"].values() for name, a, b in d["modules"]
            if readers.SCORE_PROGRAM in name]
    return 1e3 * sum(runs) * 1e-9 / len(runs) if runs else None
