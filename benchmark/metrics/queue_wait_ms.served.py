"""Mean over the window's answered requests of the batcher's own stamps,
dequeued minus enqueued, in ms."""


def read(rec):
    q = rec["window"].get("queue_wait_s")
    return float(q.mean()) * 1e3 if q is not None and len(q) else None
