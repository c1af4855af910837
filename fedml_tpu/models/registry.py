"""Name → constructor registry for the model zoo.

Mirrors the reference's ``create_model(args, model_name, output_dim)`` switch
(fedml_experiments/distributed/fedavg/main_fedavg.py:354-390) as an extensible
registry instead of an if/elif chain.
"""

from __future__ import annotations

from typing import Callable, Dict

_REGISTRY: Dict[str, Callable] = {}


def resolve_dtype(dtype):
    """'bf16'/'bfloat16' → jnp.bfloat16 (CLI-friendly); None/np dtype
    passthrough. The shared compute-dtype convention for every model
    factory that supports mixed precision."""
    if dtype in ("bf16", "bfloat16"):
        import jax.numpy as jnp

        return jnp.bfloat16
    return dtype


def register_model(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def create_model(name: str, **kwargs):
    if name not in _REGISTRY:
        # Import side-effect registration of the full zoo. Keep this list in
        # sync with the modules that exist — import errors must propagate.
        import fedml_tpu.models.cnn  # noqa: F401
        import fedml_tpu.models.darts  # noqa: F401
        import fedml_tpu.models.efficientnet  # noqa: F401
        import fedml_tpu.models.gan  # noqa: F401
        import fedml_tpu.models.granite_hybrid  # noqa: F401
        import fedml_tpu.models.k_exaone  # noqa: F401
        import fedml_tpu.models.lr  # noqa: F401
        import fedml_tpu.models.mobilenet  # noqa: F401
        import fedml_tpu.models.mobilenet_v3  # noqa: F401
        import fedml_tpu.models.nemotron_h  # noqa: F401
        import fedml_tpu.models.qwen3_next  # noqa: F401
        import fedml_tpu.models.resnet  # noqa: F401
        import fedml_tpu.models.resnet_split  # noqa: F401
        import fedml_tpu.models.rnn  # noqa: F401
        import fedml_tpu.models.transformer  # noqa: F401
        import fedml_tpu.models.unet  # noqa: F401
        import fedml_tpu.models.vfl  # noqa: F401
        import fedml_tpu.models.vgg  # noqa: F401
        import fedml_tpu.models.vit  # noqa: F401
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)
