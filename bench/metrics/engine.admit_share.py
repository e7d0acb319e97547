"""Share of the serve engine's host time spent admitting requests (prefill,
slot insert, first-token fetch): the harness's time inside
``engine.run(max_steps=1)`` less the engine's own decode-step time, over
the former, in percent."""


def read(ctx):
    c = ctx.counters
    if not c.get("engine_s"):
        return None
    return 100.0 * (c["engine_s"] - c["decode_s"]) / c["engine_s"]
