"""Real samples over the padded slots the window dispatched (cohort x step
bucket x batch): what padding to the cohort's largest client costs. 100 on a
mix of equal clients, by construction.
"""

META = {"layer": "round loop", "unit": "%", "moves": "samples_per_s_chip"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    if not summary.get("padded_slots"):
        return None
    return 100.0 * summary["real_samples"] / summary["padded_slots"]
