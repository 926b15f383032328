"""Share of the traced window in which the device ran no operation:
1 - (union of the device's operation intervals) / window, averaged over
the chips."""


def read(rec):
    trace = rec.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
