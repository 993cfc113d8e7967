"""Share of the window the public calls spent outside the compiled
engines: input building, grid and key stacking, result formatting and host
transfer. From the program's own dispatch spans (``obs.cache_stats()``):
100 * (1 - engine dispatch seconds / window seconds)."""


def read(ctx):
    if not ctx.get("window_s") or ctx.get("dispatch_s") is None:
        return None
    return 100.0 * (1.0 - ctx["dispatch_s"] / ctx["window_s"])
