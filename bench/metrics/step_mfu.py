"""step_mfu: model step, the least time the chip's peaks allow for
the window's useful work (bf16 ops at 197e12/s plus binary ops at the
int8 393e12/s, counted from the tokens prefilled and decoded), as a
share of the window (%)."""

from bench.stats import step_mfu


def read(run):
    return step_mfu(run)
