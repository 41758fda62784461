"""prefill_wave_ms: engine step, mean of the telemetry histogram
serve_prefill_wave_seconds over the window (a blocking prefill wave, from
its dispatch to its first tokens on the host)."""


def read(run):
    h = run.telemetry.prefill_s
    return 1e3 * h.mean() if h.count else None
