"""Real samples over the slots the traced rounds DISPATCHED (clients x the
step bucket each was trained at x batch), from the ``samples`` / ``slots``
arguments of the main thread's ``fed.round.dispatch`` spans: the program's
own count, the one ``dispatch_profile()`` keeps (``reduce_booked.py``).
``step_fill_pct`` computes its slots from the cohort's bucket and does not
see the size-grouped round. Cells that stream their cohorts from a host
store.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)

META = {"layer": "round loop", "unit": "%", "moves": "samples_per_s_chip"}


def applies(cell: dict) -> bool:
    return rb.rs.host_store(cell)


def read(summary: dict):
    r = rb.traced()
    if not r or not r["dispatch_args"].get("slots"):
        return None
    return 100.0 * r["dispatch_args"].get("samples", 0) \
        / r["dispatch_args"]["slots"]
