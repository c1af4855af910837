"""Peak bytes in use on the fullest chip after the window
(device.memory_stats).
"""

META = {"layer": "device", "unit": "GB", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    if not summary.get("memory_peak_bytes"):
        return None
    return summary["memory_peak_bytes"] / 1e9
