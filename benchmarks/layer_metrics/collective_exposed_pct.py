"""Share of the traced window in which a collective runs on a device and
no compute does (exclusive time of collective ops on the device's op line,
averaged over the chips)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or ctx["chips"] < 2:
        return None
    if not trace["collective_exposed_s"]:
        return None  # no collective found under a name the reduction knows
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
