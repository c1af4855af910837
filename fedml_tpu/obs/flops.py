"""Static model cost analysis.

Parity with the reference's ptflops check (fedml_api/model/cv/test_cnn.py:
1-13 prints MACs + params) via XLA's own compiled cost analysis — exact for
the graph XLA actually runs, not an operator-table estimate.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np


def count_params(params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def model_cost(model, sample_x, train: bool = False) -> Dict[str, float]:
    """{"flops", "params", "bytes_accessed"} for one forward pass of a
    registry model on ``sample_x`` (batched)."""
    from fedml_tpu.trainer.local import model_fns

    fns = model_fns(model)
    net = fns.init(jax.random.PRNGKey(0), sample_x)
    # Dropout-bearing models need an rng in train mode; a fixed key is fine
    # for a static cost analysis.
    rng = jax.random.PRNGKey(1) if train else None

    def fwd(net, x):
        logits, _ = fns.apply(net, x, train=train, rng=rng)
        return logits

    compiled = jax.jit(fwd).lower(net, sample_x).compile()
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", float("nan"))),
        "bytes_accessed": float(ca.get("bytes accessed", float("nan"))),
        "params": count_params(net.params),
    }


def flops_str(cost: Dict[str, float]) -> str:
    """Human-readable 'X.XX GMac, Y.YY M params' (ptflops format)."""
    macs = cost["flops"] / 2.0
    return f"{macs / 1e9:.2f} GMac, {cost['params'] / 1e6:.2f} M params"
