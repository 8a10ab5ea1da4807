"""The least time the chip needs for the traced brute-force search calls'
work (``work/brute_force.py``, from shapes), over the device time of
every device op inside those calls, in %."""
import peaks


def read(rec):
    tw, tr = rec.get("traced_work"), rec.get("trace")
    if not tw or not tr or rec["config"]["family"] != "brute_force":
        return None
    dev_s = tr["device_in_span_s"].get("bench.call")
    if not dev_s:
        return None
    tot = {k: sum(w[k] for w in tw) for k in ("bytes", "flops")}
    return 100.0 * peaks.least_seconds(tot, rec["peak"]())[0] / dev_s
