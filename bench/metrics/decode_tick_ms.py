"""decode_tick_ms: engine step, mean of the telemetry histogram
serve_decode_tick_seconds over the window (dispatch of the batched decode
step to its sampled tokens on the host)."""


def read(run):
    h = run.telemetry.decode_s
    return 1e3 * h.mean() if h.count else None
