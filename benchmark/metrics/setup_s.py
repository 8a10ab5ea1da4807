"""Process start to the first timed request: corpus, build, warm-up and
any compilation."""


def read(rec):
    return rec["setup_s"]
