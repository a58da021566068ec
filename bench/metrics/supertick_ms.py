"""Device time of the super-tick program per slot, averaged over devices."""

from bench import readers


def read(ctx):
    s = readers.supertick_seconds(ctx)
    slots = ctx["traced"]["slots"] if ctx.get("traced") else 0
    if s is None or not slots:
        return None
    return 1e3 * s / len(ctx["trace"]["devices"]) / slots
