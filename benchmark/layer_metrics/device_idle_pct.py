"""Share of the traced window in which no operation ran on the device, averaged
over the chips used.
"""

META = {"layer": "device", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    t = summary.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
