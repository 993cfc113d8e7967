"""Milliseconds per simulated fleet-hour spent turning the fetched outputs
into the result (the program's ``engine.format`` spans)."""
from chipbench.request_phases import ms_per_fleet_hour


def read(ctx):
    return ms_per_fleet_hour(ctx, names=("engine.format",))
