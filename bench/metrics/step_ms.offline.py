"""Device milliseconds of the engine's step programs per step in the
traced window."""
from bench import tracing
from bench.readers import STEP_PROGRAM


def read(rec):
    if rec.trace is None or not rec.trace['planes'] or not rec.steps:
        return None
    t = tracing.time_by_name(rec.trace['events'], rec.trace['planes'][0],
                             STEP_PROGRAM, line=tracing.MODULES_LINE)
    return 1e3 * t / rec.steps if t else None
