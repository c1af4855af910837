"""FedAvg-era CNNs (reference: fedml_api/model/cv/cnn.py).

- ``CNNOriginalFedAvg`` (cnn.py:5-70): McMahan'17 2-conv (32, 64 ch, 5x5) +
  FC-512 net for MNIST/FEMNIST.
- ``CNNDropOut`` (cnn.py:74-142): Reddi'20 "Adaptive Federated Optimization"
  variant with 3x3 convs, max-pool, dropout 0.25/0.5, FC-128.

NHWC layout (TPU-native; the reference is NCHW torch).

Lane-fill hooks (parallel/layout.py): both nets take
``stem="s2d"`` — a 2x2 space-to-depth input transform (1→4 channels at
half spatial), the same MXU lane-fill lever the CIFAR ResNets carry
first-class — and ``widths=(c1, c2)`` conv-width overrides, which is how
the compute-layout transform builds lane-padded physical twins.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn

from fedml_tpu.models.registry import register_model


def _stem(x, stem: str):
    if x.ndim == 3:
        x = x[..., None]
    if stem == "s2d":
        from fedml_tpu.models.resnet import space_to_depth

        return space_to_depth(x, 2)
    if stem != "conv":
        raise ValueError(f"unknown stem {stem!r}: expected conv|s2d")
    return x


class CNNOriginalFedAvg(nn.Module):
    num_classes: int = 62
    only_digits: bool = False
    stem: str = "conv"  # "conv" (reference) | "s2d" (lane-fill variant)
    widths: Any = None  # Optional[(c1, c2)] conv-width override
    hidden: int = 512
    dtype: Any = None  # compute dtype (params stay float32)
    #: im2col-rephrased stem (parallel/layout.im2col_layout builds this
    #: physical twin): the 5x5 stem conv becomes patch extraction + a
    #: 1x1 conv whose contraction dim is k²·Cin (25 on the reference
    #: stem) — the MXU sees one dense GEMM instead of a 1-channel conv.
    #: Algebraically the SAME dot per output position; the Conv_0 kernel
    #: is the (c, kh, kw)-flattened reshape of the logical 5x5 kernel.
    im2col: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = _stem(x, self.stem)
        c1, c2 = self.widths or (32, 64)
        if self.im2col:
            from jax import lax

            x = lax.conv_general_dilated_patches(
                x.astype(self.dtype or x.dtype), (5, 5), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = nn.Conv(c1, (1, 1), dtype=self.dtype)(x)
        else:
            x = nn.Conv(c1, (5, 5), padding="SAME", dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(c2, (5, 5), padding="SAME", dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.hidden, dtype=self.dtype)(x))
        return nn.Dense(10 if self.only_digits else self.num_classes,
                        dtype=self.dtype)(x)


class CNNDropOut(nn.Module):
    num_classes: int = 62
    only_digits: bool = False
    stem: str = "conv"
    widths: Any = None  # Optional[(c1, c2)]
    dtype: Any = None  # compute dtype (params stay float32)

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = _stem(x, self.stem)
        c1, c2 = self.widths or (32, 64)
        x = nn.relu(nn.Conv(c1, (3, 3), padding="VALID",
                            dtype=self.dtype)(x))
        x = nn.relu(nn.Conv(c2, (3, 3), padding="VALID",
                            dtype=self.dtype)(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Dropout(0.25, deterministic=not train)(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128, dtype=self.dtype)(x))
        x = nn.Dropout(0.5, deterministic=not train)(x)
        return nn.Dense(10 if self.only_digits else self.num_classes,
                        dtype=self.dtype)(x)


@register_model("cnn")
def _cnn(num_classes: int = 62, only_digits: bool = False,
         dropout: bool = True, stem: str = "conv", **_):
    cls = CNNDropOut if dropout else CNNOriginalFedAvg
    return cls(num_classes=num_classes, only_digits=only_digits, stem=stem)
