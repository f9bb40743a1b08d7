"""Generated tokens received by the client in the window, per second."""


def read(rec):
    return rec.tokens / rec.window_s if rec.tokens else None
