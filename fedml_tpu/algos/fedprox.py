"""FedProx — FedAvg with a proximal term μ/2·‖w − w_global‖² in the local
objective (Li et al., MLSys'20).

NOTE: the reference's fedprox snapshot does NOT actually implement the μ
term — its train loop is a verbatim FedAvg copy (SURVEY.md §2.3,
fedml_api/distributed/fedprox/MyModelTrainer.py:19-49 has no ``mu``). We
implement it properly: the proximal gradient μ(w − w_global) is added to
every local step via the trainer's ``extra_grad_fn`` hook, with ``w_global``
the round's broadcast parameters.
"""

from __future__ import annotations

import jax

from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.trainer.local import make_local_train_fn_from_cfg


class FedProxAPI(FedAvgAPI):
    """FedAvg whose LOCAL objective carries the proximal term — nothing
    else changes, so FedProx rides every execution tier FedAvg does
    (fused, windowed streaming) through the inherited "round" carry
    protocol with NO carry at all: the μ term lives inside
    ``round_fn``'s local trainer, which the windowed scan replays as-is
    (docs/EXECUTION.md support matrix; bit-equality pinned in
    tests/test_windowed.py)."""

    window_carry = "— (μ term lives in the local step)"

    def _build_local_train(self, optimizer, loss_fn):
        mu = self.cfg.fedprox_mu

        def prox_grad(params, global_params):
            return jax.tree.map(lambda p, g: mu * (p - g), params, global_params)

        return make_local_train_fn_from_cfg(
            self.fns.apply,
            optimizer,
            self.cfg,
            loss_fn,
            extra_grad_fn=prox_grad if mu > 0 else None,
        )
