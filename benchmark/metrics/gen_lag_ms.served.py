"""Mean of each request's actual send time minus its due time, in ms: how
late the load generator ran."""


def read(rec):
    lag = rec["window"].get("gen_lag_s")
    return float(lag.mean()) * 1e3 if lag is not None and len(lag) else None
