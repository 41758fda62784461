"""output_tok_s: every output token emitted in the window over the
window's seconds (host clock)."""


def read(run):
    n = sum(1 for r in run.reqs for t in r.times if run.t0 <= t <= run.t1)
    return n / run.window_s
