"""From process start to the window's first due request: weights, table,
compile or cache load, and warm-up."""


def read(rec):
    return rec.setup_s
