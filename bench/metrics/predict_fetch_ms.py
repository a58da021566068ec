"""Host time per request spent waiting for its scores on the host (the
span ``repro.serve.fetch``: the wait behind queued device work, the
serving program and the copy back), over the window, from the serving
tier's ``serve_fetch_s_total`` counter."""


def read(ctx):
    pair = ctx.get("serve_counters")
    if pair is None or "serve_fetch_s_total" not in pair[1]:
        return None
    c0, c1 = pair
    n = c1["serve_requests"] - c0["serve_requests"]
    if not n:
        return None
    return 1e3 * (c1["serve_fetch_s_total"] - c0["serve_fetch_s_total"]) / n
