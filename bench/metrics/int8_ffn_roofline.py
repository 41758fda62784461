"""int8_ffn_roofline: kernels, the binary FFN's +-1 int8 Pallas matmul
(kernels/int8_matmul.py). Least time of the window's calls (per call the
larger of its logical int8 ops at the peak and its logical bytes at HBM
bandwidth) over the device time of the kernel's ops in the trace (%)."""

from bench.stats import binary_ffn_bound_s, kernel_roofline

# the kernel's ops in the device trace of a v5e (first chip trace, PR 12)
TRACE_NAME = r"^%int8_matmul_pallas\b"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    return kernel_roofline(run, TRACE_NAME, binary_ffn_bound_s(run))
