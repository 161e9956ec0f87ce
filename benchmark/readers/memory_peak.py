"""Peak bytes in use on the fullest chip after the window, in GB; the
backend's own count (``memory_stats``), nothing where it keeps none."""


def read(params, facts):
    peak = facts["memory_peak_bytes"]
    return peak / 1e9 if peak else None
