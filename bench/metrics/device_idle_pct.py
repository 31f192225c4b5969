"""device_idle_pct: % of one traced decomposition in which no operation ran
on the device (profiler trace: 1 - union of the op intervals / window)."""


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - obs.trace.busy_s / obs.trace.window_s)
