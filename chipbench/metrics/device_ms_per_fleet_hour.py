"""Device-busy milliseconds per simulated fleet-hour in the traced window
(busy time from the profiler's trace, mean over the chips used)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["calls"]:
        return None
    return 1000.0 * tr["busy_s"] / (tr["calls"] * ctx["fleet_hours_per_call"])
