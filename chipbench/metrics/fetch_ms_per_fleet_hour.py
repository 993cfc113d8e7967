"""Milliseconds per simulated fleet-hour spent copying the engines' outputs
to the host (the program's ``engine.fetch`` spans)."""
from chipbench.request_phases import ms_per_fleet_hour


def read(ctx):
    return ms_per_fleet_hour(ctx, names=("engine.fetch",))
