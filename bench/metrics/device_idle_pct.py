"""Share of the traced window in which no op ran on the device, averaged over devices."""


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
