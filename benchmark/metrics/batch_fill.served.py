"""Mean of the batcher's ``<name>.batch_fill`` histogram over the window:
rows sent over the rows of the shape bucket they were padded to, in %."""


def read(rec):
    f = rec["window"].get("batch_fill")
    return None if f is None else 100.0 * f
