"""Model FLOPs of the window's work (the architecture's ``model_flops``:
matmuls and attention over the real context) per second of window, as % of
the chip's bf16 peak."""
from bench.readers import mfu


def read(rec):
    return mfu(rec, rec.window_s)
