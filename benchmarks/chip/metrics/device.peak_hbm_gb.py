"""device.peak_hbm_gb (GB): ``memory_stats()['peak_bytes_in_use']`` of the
fullest chip after the window, in 1e9 bytes."""


def read(ctx):
    peak = ctx["counters"].get("peak_bytes") or 0
    return peak / 1e9 if peak > 0 else None
