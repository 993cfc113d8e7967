"""Milliseconds per simulated fleet-hour spent building the engines'
per-row keys and initial state (the program's ``engine.inputs`` spans)."""
from chipbench.request_phases import ms_per_fleet_hour


def read(ctx):
    return ms_per_fleet_hour(ctx, names=("engine.inputs",))
