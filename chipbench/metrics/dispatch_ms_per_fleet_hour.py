"""Milliseconds inside the compiled engines per simulated fleet-hour,
from the program's own dispatch spans (each ends in block_until_ready)."""


def read(ctx):
    if not ctx.get("fleet_hours") or ctx.get("dispatch_s") is None:
        return None
    return 1000.0 * ctx["dispatch_s"] / ctx["fleet_hours"]
