"""Host clock around the IVF-PQ build, ending when its arrays are ready."""


def read(rec):
    return rec["build_s"] if rec["config"]["family"] == "ivf_pq" else None
