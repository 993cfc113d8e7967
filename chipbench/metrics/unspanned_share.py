"""Share of the traced window inside no child span of the program's
requests: the requests' own self time (argument checks, engine lookup)
plus the harness between calls. The named phases, the dispatch and this
share add up to the window."""
from chipbench.request_phases import unspanned_share


def read(ctx):
    return unspanned_share(ctx)
