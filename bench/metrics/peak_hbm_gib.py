"""peak_hbm_gib: KV pool and everything else on the chip, the device's
peak_bytes_in_use after the window, in GiB."""


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
