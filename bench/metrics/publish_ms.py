"""Host time per snapshot publication over the window, from the serving
tier's own counters (it includes the wait for the slot counter)."""


def read(ctx):
    pair = ctx.get("serve_counters")
    if pair is None:
        return None
    c0, c1 = pair
    n = c1["serve_snapshots_published"] - c0["serve_snapshots_published"]
    if not n:
        return None
    return 1e3 * (c1["serve_publish_s_total"] - c0["serve_publish_s_total"]) / n
