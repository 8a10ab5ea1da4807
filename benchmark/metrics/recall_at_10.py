"""Recall@10 of the sampled answers against the reference's exact ids."""


def read(rec):
    return rec["recall"]
