"""Share of its roofline the paged-attention kernel reaches: the least
time its useful work needs (valid context only, the architecture's
``paged_attention_work``) over its device time in the trace, in %."""
from bench.readers import PAGED_ATTENTION, kernel_roofline


def read(rec):
    return kernel_roofline(rec, PAGED_ATTENTION,
                           rec.arch.paged_attention_work)
