"""tick_host_ms: engine step, mean of the telemetry histogram
serve_tick_host_seconds over the window: for each engine tick that did
work, its wall time less its time in the serve.sync phase (the host
waiting for sampled tokens), which is the host's own share of the tick.
A program without the histogram reads nothing."""


def read(run):
    h = getattr(run.telemetry, "tick_host_s", None)
    return 1e3 * h.mean() if h is not None and h.count else None
