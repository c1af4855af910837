"""ResNets for federated CV workloads.

Parity targets:
- CIFAR ResNet-56/110 with Bottleneck blocks [6,6,6]/[12,12,12]
  (reference fedml_api/model/cv/resnet.py:113-246 — note the reference's
  "resnet56" is the bottleneck variant, 16→64 widths; we mirror that).
- ImageNet-style ResNet-18/34/50/101/152 with **GroupNorm** (reference
  fedml_api/model/cv/resnet_gn.py:108-235, default 32 channels/group, used
  for fed_cifar100 per Reddi'20).

TPU-first choices: NHWC layout, GroupNorm default (BatchNorm running stats
are a known FL pathology — the reference's robust aggregator special-cases
them, fedml_core/robustness/robust_aggregation.py:27-29; a ``norm='bn'``
variant is provided for strict parity and its batch_stats ride NetState).

KNOWN LIMITATION of ``norm='bn'`` with ragged clients: padded duplicate
samples inside a partially-masked batch enter the BatchNorm batch
statistics (the mask guards losses and optimizer updates, not the forward
normalization). With per-client sample counts that are multiples of the
batch size this is exact; otherwise prefer GroupNorm (the default, and the
setting the reference's published fed_cifar100 baseline uses).
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.models.registry import register_model


def norm_groups(c: int, groups: int = 32) -> int:
    """The GroupNorm group-count policy: the largest divisor of the
    channel count that is <= ``groups`` (reference group_normalization.py
    defaults to 32 ch/group on power-of-two widths; MobileNetV3/
    EfficientNet widths like 72/88/200 need the divisor search). Single
    source — ``parallel/layout.py`` reads the same policy to keep a
    lane-padded physical twin's grouping exact."""
    g = min(groups, c)
    while c % g:
        g -= 1
    return g


class Norm(nn.Module):
    """GroupNorm (32 groups, clipped to channel count), BatchNorm,
    ``"gn_fused"`` (the pallas fused GroupNorm kernel,
    fedml_tpu.ops.group_norm — same math and param tree as ``"gn"``;
    measured SLOWER than XLA's conv-fused lowering at CIFAR-ResNet
    shapes, so not the default — ops/group_norm.py), or ``"none"``
    (identity — an ablation that attributes normalization cost by
    leaving it out; not a training configuration).

    ``logical_channels`` (lane-fill compute layouts,
    ``parallel/layout.py``): when the module runs a lane-PADDED physical
    channel count, the group size must stay what the LOGICAL model's
    policy chose — logical channels keep their exact grouping (bit-equal
    statistics) and the zero pad channels fill whole extra groups of the
    same size, where they normalize to exactly zero. 0 = physical is
    logical (the default, byte-identical to the pre-layout behavior)."""

    kind: str = "gn"
    groups: int = 32
    dtype: Any = None  # compute dtype (params stay float32)
    logical_channels: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.kind == "none":
            return x
        if self.kind == "bn":
            return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                dtype=self.dtype)(x)
        c = x.shape[-1]
        c_log = self.logical_channels or c
        cpg = c_log // norm_groups(c_log, self.groups)
        if c % cpg:
            raise ValueError(
                f"padded channel count {c} is not a multiple of the "
                f"logical group size {cpg} (logical {c_log} channels): "
                "pad channels in whole-group quanta or the logical "
                "statistics change (parallel/layout.py pads accordingly)")
        g = c // cpg
        if self.kind == "gn_fused":
            # name="GroupNorm_0" matches nn.GroupNorm's auto-name in the
            # "gn" branch → identical param trees; checkpoints are
            # interchangeable between the two kinds.
            return _GroupNormFused(num_groups=g, dtype=self.dtype,
                                   name="GroupNorm_0")(x)
        return nn.GroupNorm(num_groups=g, dtype=self.dtype)(x)


class _GroupNormFused(nn.Module):
    """nn.GroupNorm drop-in backed by the pallas fused kernel
    (fedml_tpu.ops.group_norm): same params (scale/bias), same f32-stats
    numerics, one VMEM pass fwd and one fused backward."""

    num_groups: int
    dtype: Any = None
    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        from fedml_tpu.ops.group_norm import group_norm

        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        return group_norm(x.astype(self.dtype or x.dtype), scale, bias,
                          self.num_groups, self.epsilon)


class BottleneckBlock(nn.Module):
    #: ``logical_planes`` (lane-fill layouts): the LOGICAL width this
    #: block's ``planes`` was padded up from — forwarded to every Norm so
    #: the padded twin keeps the logical grouping. 0 = planes is logical.
    planes: int
    strides: int = 1
    norm: str = "gn"
    expansion: int = 4
    dtype: Any = None
    logical_planes: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False):
        lp = self.logical_planes
        residual = x
        y = nn.Conv(self.planes, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = Norm(self.norm, dtype=self.dtype, logical_channels=lp)(y, train)
        y = nn.relu(y)
        # Explicit (1,1) padding == torch conv3x3(padding=1): identical to
        # "SAME" at stride 1, and at stride 2 it keeps the reference's
        # sampling grid (SAME would pad (0,1) and shift the windows) — so
        # converted torch checkpoints reproduce outputs exactly.
        y = nn.Conv(self.planes, (3, 3), (self.strides, self.strides),
                    padding=((1, 1), (1, 1)), use_bias=False,
                    dtype=self.dtype)(y)
        y = Norm(self.norm, dtype=self.dtype, logical_channels=lp)(y, train)
        y = nn.relu(y)
        y = nn.Conv(self.planes * self.expansion, (1, 1), use_bias=False,
                    dtype=self.dtype)(y)
        y = Norm(self.norm, dtype=self.dtype,
                 logical_channels=lp * self.expansion)(y, train)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.planes * self.expansion, (1, 1),
                (self.strides, self.strides), use_bias=False, name="downsample",
                dtype=self.dtype,
            )(x)
            residual = Norm(self.norm, dtype=self.dtype,
                            logical_channels=lp * self.expansion)(
                residual, train)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    planes: int
    strides: int = 1
    norm: str = "gn"
    expansion: int = 1
    dtype: Any = None
    logical_planes: int = 0  # see BottleneckBlock

    @nn.compact
    def __call__(self, x, train: bool = False):
        lp = self.logical_planes
        residual = x
        # torch conv3x3(padding=1) grid — see BottleneckBlock.
        y = nn.Conv(self.planes, (3, 3), (self.strides, self.strides),
                    padding=((1, 1), (1, 1)), use_bias=False,
                    dtype=self.dtype)(x)
        y = Norm(self.norm, dtype=self.dtype, logical_channels=lp)(y, train)
        y = nn.relu(y)
        y = nn.Conv(self.planes, (3, 3), padding=((1, 1), (1, 1)),
                    use_bias=False, dtype=self.dtype)(y)
        y = Norm(self.norm, dtype=self.dtype, logical_channels=lp)(y, train)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.planes, (1, 1), (self.strides, self.strides),
                use_bias=False, name="downsample", dtype=self.dtype,
            )(x)
            residual = Norm(self.norm, dtype=self.dtype,
                            logical_channels=lp)(residual, train)
        return nn.relu(residual + y)


def space_to_depth(x, block: int = 2):
    """[B, H, W, C] → [B, H/b, W/b, C·b²]: move 2x2 spatial patches into
    channels — the classic TPU transform for small-channel CNN stems
    (narrow early stages under-fill the 128-lane MXU; see
    parallel/layout.py)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    return jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(
        b, h // block, w // block, c * block * block)


class CifarResNet(nn.Module):
    """CIFAR-style 3-stage ResNet (reference resnet.py:113-200).

    ``stem="s2d"`` is the TPU-friendly variant the roofline analysis
    names as the first lever against lane under-fill: a 2x2
    space-to-depth input transform (3→12 channels, 32→16 spatial) with
    stage widths doubled to (32, 64, 128). Per-conv FLOPs stay ~equal
    (H·W·C² is invariant under half-spatial/double-channel), but every
    stage's channel count doubles its MXU lane fill — stage 3 fills all
    128 lanes. NOT the reference model (4x params per conv): the
    benchmark's ResNet-56 cells keep the standard stem, and no cell
    runs this variant."""

    layers: Sequence[int] = (6, 6, 6)  # 56 = 6*3*3 + 2
    num_classes: int = 10
    norm: str = "gn"
    dtype: Any = None  # compute dtype; jnp.bfloat16 = mixed precision
    stem: str = "conv"  # "conv" (reference) | "s2d" (TPU lane-fill variant)
    #: Stage-width / stem-channel overrides (None/0 = the stem kind's
    #: defaults). ``parallel/layout.py`` builds lane-padded physical
    #: twins through these; they also admit deliberately non-reference
    #: widths for lane-fill measurement models.
    widths: Any = None  # Optional[Tuple[int, int, int]]
    stem_width: int = 0
    #: Set by the layout transform on a PADDED twin: the logical widths
    #: the physical ones were padded up from, threaded to every Norm so
    #: grouping (and therefore the math on the logical channels) stays
    #: bit-identical to the logical model. None/0 = widths are logical.
    logical_widths: Any = None
    logical_stem: int = 0

    def stage_widths(self):
        """(stem_ch, per-stage widths) after overrides — the shapes the
        param tree will carry (layout planning reads this)."""
        if self.stem == "s2d":
            widths, stem_ch = (32, 64, 128), 32
        elif self.stem == "conv":
            widths, stem_ch = (16, 32, 64), 16
        else:
            raise ValueError(f"unknown stem {self.stem!r}: expected conv|s2d")
        return (self.stem_width or stem_ch,
                tuple(self.widths) if self.widths else widths)

    @nn.compact
    def __call__(self, x, train: bool = False):
        stem_ch, widths = self.stage_widths()
        log_w = tuple(self.logical_widths) if self.logical_widths \
            else (0,) * len(widths)
        if self.stem == "s2d":
            x = space_to_depth(x, 2)
        x = nn.Conv(stem_ch, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(x)
        x = Norm(self.norm, dtype=self.dtype,
                 logical_channels=self.logical_stem)(x, train)
        x = nn.relu(x)
        for stage, (planes, n_blocks) in enumerate(zip(widths, self.layers)):
            for i in range(n_blocks):
                strides = 2 if (stage > 0 and i == 0) else 1
                x = BottleneckBlock(planes, strides, self.norm,
                                    dtype=self.dtype,
                                    logical_planes=log_w[stage])(x, train)
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


class ResNetGN(nn.Module):
    """ImageNet-style ResNet with GroupNorm (reference resnet_gn.py:108-235),
    stem adapted for small inputs when ``small_input`` (fed_cifar100 runs
    24x24 crops through the ImageNet stem in the reference; we keep that
    possible but default to a 3x3 stem for 32x32)."""

    stage_sizes: Sequence[int] = (2, 2, 2, 2)  # resnet18
    block: str = "basic"  # "basic" | "bottleneck"
    num_classes: int = 100
    norm: str = "gn"
    small_input: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.small_input:
            x = nn.Conv(64, (3, 3), padding="SAME", use_bias=False,
                        dtype=self.dtype)(x)
        else:
            x = nn.Conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
        x = Norm(self.norm, dtype=self.dtype)(x, train)
        x = nn.relu(x)
        if not self.small_input:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        blk = BasicBlock if self.block == "basic" else BottleneckBlock
        for stage, n_blocks in enumerate(self.stage_sizes):
            planes = 64 * (2 ** stage)
            for i in range(n_blocks):
                strides = 2 if (stage > 0 and i == 0) else 1
                x = blk(planes, strides, self.norm, dtype=self.dtype)(x, train)
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


from fedml_tpu.models.registry import resolve_dtype as _dt  # noqa: E402


@register_model("resnet56")
def resnet56(num_classes: int = 10, norm: str = "gn", dtype=None,
             stem: str = "conv", widths=None, **_):
    return CifarResNet(layers=(6, 6, 6), num_classes=num_classes, norm=norm,
                       dtype=_dt(dtype), stem=stem, widths=widths)


@register_model("resnet56_s2d")
def resnet56_s2d(num_classes: int = 10, norm: str = "gn", dtype=None, **_):
    """The measured lane-fill variant as a first-class registry name
    (CLI: ``--model resnet56_s2d``): 2x2 space-to-depth stem, stage
    widths doubled — ~3.2x the reference stem's samples/sec through the
    retired attachment (no ledger line) at equal per-conv FLOPs. NOT
    weight-compatible with the reference model (4x params per conv) —
    ``torch_convert`` refuses reference checkpoints for it loudly."""
    return CifarResNet(layers=(6, 6, 6), num_classes=num_classes, norm=norm,
                       dtype=_dt(dtype), stem="s2d")


@register_model("resnet110")
def resnet110(num_classes: int = 10, norm: str = "gn", dtype=None,
              stem: str = "conv", **_):
    return CifarResNet(layers=(12, 12, 12), num_classes=num_classes, norm=norm,
                       dtype=_dt(dtype), stem=stem)


@register_model("resnet20")
def resnet20(num_classes: int = 10, norm: str = "gn", dtype=None,
             stem: str = "conv", widths=None, **_):
    """Small CIFAR ResNet (2-2-2 bottleneck) — test/dryrun workhorse."""
    return CifarResNet(layers=(2, 2, 2), num_classes=num_classes, norm=norm,
                       dtype=_dt(dtype), stem=stem, widths=widths)


@register_model("resnet10_gn")
def resnet10_gn(num_classes: int = 100, **_):
    """Reduced-depth ResNet-GN (one basic block per stage): the
    ``CI_LITE_DEPTH`` compile proxy for the fed_cifar100 row — same
    4-stage GroupNorm architecture, loader path, and flag wiring as
    resnet18_gn at a CPU-compilable depth, so ``reproduce_baselines.sh
    fed_cifar100_resnet18`` is exercised in CI instead of documented as
    too slow (REPRO.md CI-lite table)."""
    return ResNetGN(stage_sizes=(1, 1, 1, 1), block="basic", num_classes=num_classes)


@register_model("resnet18_gn")
def resnet18_gn(num_classes: int = 100, **_):
    return ResNetGN(stage_sizes=(2, 2, 2, 2), block="basic", num_classes=num_classes)


@register_model("resnet34_gn")
def resnet34_gn(num_classes: int = 100, **_):
    return ResNetGN(stage_sizes=(3, 4, 6, 3), block="basic", num_classes=num_classes)


@register_model("resnet50_gn")
def resnet50_gn(num_classes: int = 100, **_):
    return ResNetGN(stage_sizes=(3, 4, 6, 3), block="bottleneck", num_classes=num_classes)


@register_model("resnet101_gn")
def resnet101_gn(num_classes: int = 100, **_):
    return ResNetGN(stage_sizes=(3, 4, 23, 3), block="bottleneck", num_classes=num_classes)


@register_model("resnet152_gn")
def resnet152_gn(num_classes: int = 100, **_):
    return ResNetGN(stage_sizes=(3, 8, 36, 3), block="bottleneck", num_classes=num_classes)
