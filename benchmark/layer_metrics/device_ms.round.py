"""Device time a round: the union of the first device's XLA Ops intervals over
the traced rounds.
"""

META = {"layer": "client step", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    t = summary.get("trace")
    if not t:
        return None
    return 1e3 * t["busy_s_first"] / t["rounds"]
