"""flash_attn_roofline: kernels, the Pallas flash attention of monolithic
prefill (kernels/flash_attention.py). Least time of the window's calls
(causal ops over the prompts' true lengths at the bf16 peak, or Q, K, V
and O bytes at HBM bandwidth, whichever is larger) over the device time
of the kernel's ops in the trace (%)."""

from bench.stats import flash_bound_s, kernel_roofline

# the kernel's ops in the device trace of a v5e (first chip trace, PR 12)
TRACE_NAME = r"^%_flash_call\b"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    return kernel_roofline(run, TRACE_NAME, flash_bound_s(run))
