"""Shared kernel-timing machinery for the measurement scripts.

``calibrated_ramp`` measures seconds/iteration of a chained-op jit whose
per-op cost may be MICROSECONDS — far below a ~0.1 s fixed per-call
dispatch cost, where a small fixed two-point probe cannot resolve the
slope. Method: ramp the chain length exponentially until a call clearly
exceeds that band, two-point fit between the last two ramp lengths
(cancels the constant per-call cost), then time at the target length and enforce
the device-work floor.

Extracted from sweep_filter_grad.py / sweep_gn_standalone.py (r5 review:
the two copies had already needed one lockstep fix).
"""

import time


def calibrated_ramp(run, floor_s=0.4, target_s=0.6, ramp_cap=1 << 22,
                    iters_cap=1 << 24):
    """Median seconds/iter of ``run(iters)`` (which must block until the
    device work is done, e.g. by returning a host-fetched scalar)."""
    import numpy as np

    def call(iters):
        t0 = time.perf_counter()
        float(run(iters))
        return time.perf_counter() - t0

    call(1)  # compile
    n_prev, t_prev = 1, min(call(1) for _ in range(2))
    n, ramp = 8, []
    # Ramp-exit thresholds derived from the caller's floor/target (r5
    # ADVICE: hardcoded 0.5/0.2 ignored a larger requested floor_s, so
    # the slope could be fitted from calls below the device-work floor
    # the caller asked for): the call must carry most of the target's
    # work AND the last quadrupling must have added clearly more than
    # the RTT band before the two-point fit is trusted.
    exit_t, exit_dt = target_s * 0.8, floor_s / 2
    while n <= ramp_cap:
        t = min(call(n) for _ in range(2))
        ramp.append((n, t))
        if t >= exit_t and t - t_prev > exit_dt:
            break
        n_prev, t_prev = n, t
        n *= 4
    else:
        raise RuntimeError(f"ramp exhausted: {ramp}")
    per_iter = (t - t_prev) / (n - n_prev)
    rtt = max(t_prev - per_iter * n_prev, 0.0)
    for _ in range(5):
        iters = max(1, min(iters_cap, int(np.ceil(target_s / per_iter))))
        meds = sorted(call(iters) for _ in range(5))
        med = meds[2]
        refined = max((med - rtt) / iters, 1e-9)
        if refined * iters >= floor_s:
            return refined
        per_iter = refined
    raise RuntimeError("floor not reached")
