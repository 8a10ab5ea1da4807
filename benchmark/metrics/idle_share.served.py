"""Share of the traced window in which no op ran on the device, in %."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
