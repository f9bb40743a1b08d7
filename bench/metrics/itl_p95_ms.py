"""95th percentile of the gaps between consecutive tokens of a request,
over every gap that ends in the window, in ms."""
from bench.readers import percentile


def read(rec):
    p = percentile(rec.itl_s, 95)
    return None if p is None else 1e3 * p
