"""`hbm_peak_gb`: the run's `memory_peak_bytes` of the fullest chip, read once
the window has closed and before the reference runs: `peak_bytes_in_use +
peak_bytes_reserved` of `memory_stats()` (live buffers and loaded code; the
executables' scratch arena).  Two high-water marks, so their sum is an upper
bound on the true peak."""
UNIT = "GB"


def read(run: dict):
    peak = run["result"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
