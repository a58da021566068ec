"""Host time per request spent checking its ids, routing them and copying
the gather plan and the features to the device (the span
``repro.serve.route``), over the window, from the serving tier's
``serve_route_s_total`` counter."""


def read(ctx):
    pair = ctx.get("serve_counters")
    if pair is None or "serve_route_s_total" not in pair[1]:
        return None
    c0, c1 = pair
    n = c1["serve_requests"] - c0["serve_requests"]
    if not n:
        return None
    return 1e3 * (c1["serve_route_s_total"] - c0["serve_route_s_total"]) / n
