"""Host time per snapshot publication spent waiting for the slot counter
(the span ``repro.serve.publish.sync``), over the window, from the
serving tier's ``serve_publish_sync_s_total`` counter. ``publish_ms``
less this is the publication proper."""


def read(ctx):
    pair = ctx.get("serve_counters")
    if pair is None or "serve_publish_sync_s_total" not in pair[1]:
        return None
    c0, c1 = pair
    n = c1["serve_snapshots_published"] - c0["serve_snapshots_published"]
    if not n:
        return None
    return 1e3 * (c1["serve_publish_sync_s_total"] - c0["serve_publish_sync_s_total"]) / n
