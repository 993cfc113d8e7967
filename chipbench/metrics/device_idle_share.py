"""Share of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy being the union of the device's operation
intervals (mean over the chips used)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
