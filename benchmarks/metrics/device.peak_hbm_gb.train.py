"""`memory_stats()["peak_bytes_in_use"]` after the window, in GB. It counts
live buffers; the step program's own need is device.step_program_hbm_gb."""


def read(run):
    peak = run["result"].get("memory_peak_bytes")
    if peak is None or "losses" not in run["samples"]:
        return None
    return peak / 1e9
