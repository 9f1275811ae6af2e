"""Device: share of the traced window in which no operation ran on the
chip, from the profiler trace, in %."""


def read(rec):
    if not rec.window_s or rec.busy_s is None:
        return None
    return 100.0 * (1.0 - rec.busy_s / rec.window_s)
