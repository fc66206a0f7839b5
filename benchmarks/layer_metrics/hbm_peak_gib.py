"""Peak device memory after the window, as the backend reports it."""


def read(run: dict):
    peak = run["memory_peak_bytes"]
    return peak / 2**30 if peak else None
