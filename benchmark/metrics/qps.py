"""Queries answered in the window over the window's seconds."""


def read(rec):
    w = rec["window"]
    return w["queries"] / w["elapsed_s"] if w.get("elapsed_s") else None
