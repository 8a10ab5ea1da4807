"""Host clock around the brute-force build, ending when its arrays are
ready."""


def read(rec):
    return rec["build_s"] if rec["config"]["family"] == "brute_force" else None
