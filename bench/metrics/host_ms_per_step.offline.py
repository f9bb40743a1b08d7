"""Host milliseconds per engine step in the window, from the engine's own
phase histograms (host_schedule + radix_lookup + pack_layout + dispatch;
their sums are exact): scheduling work the device may wait for."""

PHASES = ('host_schedule', 'radix_lookup', 'pack_layout', 'dispatch')


def read(rec):
    if rec.telemetry is None or not rec.steps:
        return None
    ph = rec.telemetry['phase_s']
    return 1e3 * sum(ph.get(p, 0.0) for p in PHASES) / rec.steps
