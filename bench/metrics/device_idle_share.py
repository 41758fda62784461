"""device_idle_share: device, 1 - (union of device-op intervals /
traced window), from the profiler trace of the window (%)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
