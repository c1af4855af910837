"""Time a round inside collective operations on the first device (union of
their XLA Ops intervals over the traced rounds). Exists only across chips.
"""

META = {"layer": "client parallelism", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return int(cell["chips"]) > 1


def read(summary: dict):
    t = summary.get("trace")
    if not t or summary["chips"] < 2:
        return None
    return 1e3 * t["collective_s_first"] / t["rounds"]
