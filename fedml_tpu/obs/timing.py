"""Round timing + profiling.

The reference logs coarse aggregation wall-clock (FedAVGAggregator.py:60,
86-87) and nothing else. Here timing is a first-class subsystem:

- ``RoundTimer`` — per-phase wall-clock with jax ``block_until_ready``
  fencing so device work is actually measured (an async dispatch would
  otherwise clock ~0);
- ``trace`` — context manager around ``jax.profiler`` producing a
  TensorBoard-loadable XLA trace directory for the real TPU hot loop.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List


class RoundTimer:
    """Usage::

        t = RoundTimer()
        with t.phase("local_train"):
            out = round_fn(...)
            t.fence(out)          # block_until_ready inside the phase
        t.summary()  # {"local_train": {"mean_s": ..., "total_s": ..., "n": ...}}
    """

    def __init__(self):
        self._acc: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self._acc.setdefault(name, []).append(time.perf_counter() - t0)

    def fence(self, tree):
        import jax

        jax.block_until_ready(tree)

    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for k, v in self._acc.items():
            out[k] = {
                "mean_s": sum(v) / len(v),
                "total_s": sum(v),
                "n": len(v),
                "last_s": v[-1],
            }
        return out

    def mark(self):
        """Snapshot phase counts; ``flat_metrics`` then reports only phases
        that recorded since the mark (so a round that ran no eval does not
        re-log the previous eval's duration)."""
        self._mark = {k: len(v) for k, v in self._acc.items()}

    def flat_metrics(self) -> Dict[str, float]:
        """{"time/<phase>_s": last} for phases recorded since ``mark()``
        (all phases if ``mark`` was never called)."""
        mark = getattr(self, "_mark", {})
        return {
            f"time/{k}_s": v[-1]
            for k, v in self._acc.items()
            if len(v) > mark.get(k, 0)
        }


@contextlib.contextmanager
def trace(log_dir: str):
    """XLA/TPU profiler trace into ``log_dir`` (an ``.xplane.pb`` under
    ``plugins/profile/<time>/``; view in TensorBoard / xprof, or read with
    ``jax.profiler.ProfileData.from_file``). A trace that was asked for
    and cannot be started or written is a failure: ``start_trace`` /
    ``stop_trace`` errors propagate."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
