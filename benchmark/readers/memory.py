"""Peak device memory: memory_stats()["peak_bytes_in_use"], the fullest of
the cell's chips, read after the window."""


def read(metric: dict, obs: dict):
    peak = obs.get("device", {}).get("memory_peak_bytes")
    if not peak:
        return None
    return peak / 1e9
