"""Milliseconds per simulated fleet-hour spent building a sweep's grid on
the host: the self time of the program's ``sweep.grid``, ``scenario.*``
and ``sweep.stack`` spans. None where the calls build no grid (``run``)."""
from chipbench.request_phases import ms_per_fleet_hour


def read(ctx):
    return ms_per_fleet_hour(ctx, names=("sweep.grid", "sweep.stack"),
                             prefixes=("scenario.",))
