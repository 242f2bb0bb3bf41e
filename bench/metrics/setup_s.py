"""Set-up time: process start to the first timed item (JAX start, compile
or cache load, inputs made from the seed, warm-up)."""


def read(rec):
    return rec["setup_s"]
