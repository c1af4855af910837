"""Lane-fill compute layouts: logical model, lane-aligned client step.

Why the CIFAR CNN hot path under-delivers: channel dims below the MXU's
128-lane width leave lanes idle (a reading through the retired attachment,
not re-measured on this benchmark). This module makes channel-dim padding a
FRAMEWORK capability instead of a per-model fork, with a hard invisibility
contract:

- the **logical** model — what clients train against, servers aggregate,
  checkpoints store, the wire ships, and every bit-equality pin sees —
  keeps its reference shapes everywhere;
- the jitted client step runs a **physical** twin whose channel dims are
  padded up to lane/sublane-friendly multiples, via a pure pad-on-entry /
  slice-on-exit wrapper around the local trainer
  (:func:`wrap_local_train`). Padding never crosses the client-step
  boundary.

The padded twin is EXACT, not approximate (tested bit-equal in fp32,
tests/test_layout.py): every padded parameter entry is zero and *stays*
zero through training — zero input-channel slices contribute nothing
forward, and the zero output-filters receive zero gradient back (the
classifier's padded input rows are zero, so no gradient ever reaches a
padded channel). GroupNorm is the one layer where padding could leak:
the pad channels must fill WHOLE extra groups of the logical group size
(``models/resnet.Norm(logical_channels=...)``), where they normalize to
exactly zero; :func:`pad_channels` bakes that constraint into the pad
quantum. Dropout-bearing models are REFUSED: their mask draw shapes
follow the physical layout, so padded-vs-logical exactness is
unattainable by construction.

When padding pays vs hurts (measured — docs/EXECUTION.md "MFU
playbook"): the MXU charges a full 128-lane pass whatever the channel
count, so padding an already-small dim (16 → 128) multiplies FLOPs
without moving wall-clock; padding pays on dims sitting just UNDER a
lane multiple (96/120 → 128) and is near-free otherwise. MFU accounting
here is always against the LOGICAL model's FLOPs — padding can never
inflate the numerator.

Supported families: ``CifarResNet`` (gn/bn/none norms) and
``CNNOriginalFedAvg``. Others refuse loudly. Space-to-depth stems
(``stem="s2d"``) compose — s2d trades spatial extent for channel depth
at constant FLOPs and remains the first lever; this transform squares
up whatever widths remain misaligned.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LayoutPolicy:
    """The pad policy: round channel dims up to ``sublane`` multiples,
    and snap to the next ``lane`` multiple when already within
    ``lane_snap`` of it (96 → 128 at the default 0.25; 16 stays 16 —
    padding 8x the FLOPs for an already-paid lane pass
    hurts)."""

    lane: int = 128
    sublane: int = 8
    lane_snap: float = 0.25


def pad_width(c: int, policy: LayoutPolicy) -> int:
    """The policy's target physical width for a logical channel count
    (before any GroupNorm group-quantum constraint)."""
    target = -(-c // policy.sublane) * policy.sublane
    next_lane = -(-c // policy.lane) * policy.lane
    if (next_lane - c) <= policy.lane_snap * policy.lane:
        target = max(target, next_lane)
    return target


def pad_channels(c: int, policy: LayoutPolicy, quanta: Tuple[int, ...] = ()
                 ) -> int:
    """Smallest physical width >= the policy target that is a multiple of
    the sublane AND of every ``quanta`` entry (GroupNorm group sizes at
    each scale the width appears at — pad channels must fill whole
    groups or the logical statistics change). Never below ``c``."""
    q = math.lcm(policy.sublane, *quanta) if quanta else policy.sublane
    target = max(pad_width(c, policy), c)
    p = -(-target // q) * q
    return max(p, c)


def _pad_spec(logical_shape, physical_shape):
    if len(logical_shape) != len(physical_shape) or any(
            p < l for l, p in zip(logical_shape, physical_shape)):
        raise ValueError(
            f"physical leaf {physical_shape} does not embed logical "
            f"{logical_shape}")
    return tuple((0, p - l) for l, p in zip(logical_shape, physical_shape))


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@dataclass
class ComputeLayout:
    """The logical↔physical mapping for one model: a physical twin
    module plus pure, jit-traceable ``pad`` (embed logical params into
    the zero-initialized physical tree) and ``unpad`` (slice the logical
    block back out). ``pad``/``unpad`` operate on ``NetState``-shaped
    pytrees (params + model_state) and are exact inverses on the
    logical block."""

    logical_model: Any
    physical_model: Any
    #: path-string → (pad_leaf, unpad_leaf) overrides for leaves whose
    #: logical block is not a leading slice (flatten-boundary Dense
    #: kernels interleave channels into the row index).
    overrides: Dict[str, Tuple[Callable, Callable]] = field(
        default_factory=dict)
    #: flatten-order-aligned per-leaf records, built by ``_build_specs``:
    #: (path string, logical shape, pad spec or None-for-override)
    _leaves: Any = None

    @property
    def is_identity(self) -> bool:
        return not self.overrides and all(
            spec is not None and not any(hi for _, hi in spec)
            for _, _, spec in self._leaves)

    def _build_specs(self, sample_x):
        from fedml_tpu.trainer.local import model_fns

        sample = sample_x if hasattr(sample_x, "dtype") else \
            jax.ShapeDtypeStruct(np.shape(sample_x),
                                 np.asarray(sample_x).dtype)
        key = jax.ShapeDtypeStruct((2,), np.uint32)

        def shapes(module):
            fns = model_fns(module)
            return jax.eval_shape(lambda k, x: fns.init(k, x), key, sample)

        log, phys = shapes(self.logical_model), shapes(self.physical_model)
        paths_l, treedef_l = jax.tree_util.tree_flatten_with_path(log)
        paths_p, treedef_p = jax.tree_util.tree_flatten_with_path(phys)
        if treedef_l != treedef_p:
            raise ValueError(
                "logical and physical models have different param trees")
        leaves = []
        for (pl, ll), (pp, lp) in zip(paths_l, paths_p):
            if ll.dtype != lp.dtype:
                raise ValueError(
                    f"{_path_str(pl)}: dtype drift {ll.dtype} vs {lp.dtype}")
            path = _path_str(pl)
            spec = None if path in self.overrides \
                else _pad_spec(ll.shape, lp.shape)
            leaves.append((path, tuple(ll.shape), spec))
        unknown = set(self.overrides) - {p for p, _, _ in leaves}
        if unknown:
            raise ValueError(f"override paths not in the param tree: "
                             f"{sorted(unknown)}")
        self._leaves = leaves

    def _apply(self, net, which: int):
        paths, treedef = jax.tree_util.tree_flatten_with_path(net)
        if len(paths) != len(self._leaves):
            raise ValueError(
                f"net has {len(paths)} leaves, layout expects "
                f"{len(self._leaves)}")
        out = []
        for (p, leaf), (path, shape, spec) in zip(paths, self._leaves):
            if _path_str(p) != path:
                raise ValueError(
                    f"leaf order mismatch: {_path_str(p)} vs {path}")
            if spec is None:
                out.append(self.overrides[path][which](leaf))
            elif which == 0:  # pad
                out.append(jnp.pad(leaf, spec)
                           if any(hi for _, hi in spec) else leaf)
            else:  # unpad
                out.append(leaf if tuple(leaf.shape) == shape else
                           leaf[tuple(slice(0, s) for s in shape)])
        return jax.tree.unflatten(treedef, out)

    def pad(self, net):
        """Logical NetState → physical (zero-fill the pad block). Pure;
        traced inside the jitted client step."""
        return self._apply(net, 0)

    def unpad(self, net):
        """Physical NetState → logical (slice the leading block)."""
        return self._apply(net, 1)

    def describe(self) -> Dict[str, Any]:
        """Machine-readable summary (bench/docs): logical param count
        and how many leaves carry pad."""
        padded = sum(1 for _, _, s in self._leaves
                     if s is None or any(hi for _, hi in s))
        return {"leaves": len(self._leaves), "padded_leaves": padded,
                "logical_params": int(sum(
                    np.prod(s) for _, s, _ in self._leaves)),
                "identity": self.is_identity}


# --- model-family physical-twin builders ------------------------------

def _cifar_resnet_twin(model, policy: LayoutPolicy):
    from fedml_tpu.models.resnet import norm_groups

    if model.norm not in ("gn", "bn", "none"):
        raise NotImplementedError(
            f"compute_layout supports CifarResNet norm in gn|bn|none; "
            f"got {model.norm!r}")
    if model.logical_widths or model.logical_stem:
        raise ValueError("model is already a padded physical twin")
    stem_ch, widths = model.stage_widths()
    gn = model.norm == "gn"

    def quanta(width, scales):
        # GroupNorm sites this stage width feeds (x1 for the in-block
        # norms, x expansion for the block output): a physical width p
        # appears at each site as p*scale channels, which must hold
        # whole logical groups — (p*scale) % cpg(w*scale) == 0, i.e.
        # p % (cpg / gcd(scale, cpg)) == 0.
        if not gn:
            return ()
        out = []
        for scale in scales:
            c = width * scale
            cpg = c // norm_groups(c)
            out.append(cpg // math.gcd(scale, cpg))
        return tuple(out)

    e = 4  # BottleneckBlock expansion
    p_widths = tuple(pad_channels(w, policy, quanta(w, (1, e)))
                     for w in widths)
    p_stem = pad_channels(stem_ch, policy, quanta(stem_ch, (1,)))
    if p_widths == tuple(widths) and p_stem == stem_ch:
        return model  # identity
    return type(model)(
        layers=tuple(model.layers), num_classes=model.num_classes,
        norm=model.norm, dtype=model.dtype, stem=model.stem,
        widths=p_widths, stem_width=p_stem,
        logical_widths=tuple(widths), logical_stem=stem_ch), {}


def _cnn_original_twin(model, policy: LayoutPolicy, sample_x):
    c1, c2 = model.widths or (32, 64)
    p1, p2 = pad_channels(c1, policy), pad_channels(c2, policy)
    if (p1, p2) == (c1, c2):
        return model
    twin = type(model)(num_classes=model.num_classes,
                       only_digits=model.only_digits, stem=model.stem,
                       widths=(p1, p2), hidden=model.hidden)
    # Flatten boundary: Dense_0's kernel rows interleave (h, w, channel)
    # — a tail pad would bind logical weights to the wrong physical
    # rows. Pad/slice the channel axis through a reshape instead.
    shape = np.shape(sample_x)
    h, w = shape[1], shape[2]
    if model.stem == "s2d":
        h, w = h // 2, w // 2
    h, w = h // 4, w // 4  # two 2x2 max-pools on SAME convs
    hidden = twin.hidden

    def pad_dense(leaf):
        k = leaf.reshape(h, w, c2, hidden)
        return jnp.pad(k, ((0, 0), (0, 0), (0, p2 - c2), (0, 0))).reshape(
            h * w * p2, hidden)

    def unpad_dense(leaf):
        return leaf.reshape(h, w, p2, hidden)[:, :, :c2].reshape(
            h * w * c2, hidden)

    return twin, {".params/Dense_0/kernel": (pad_dense, unpad_dense)}


def compute_layout(model, sample_x, *, lane: int = 128, sublane: int = 8,
                   lane_snap: float = 0.25):
    """Build the lane-fill :class:`ComputeLayout` for a supported model,
    or raise ``NotImplementedError`` naming the supported families.
    Returns a layout whose ``is_identity`` is True when the policy pads
    nothing (callers then skip the wrapper entirely).

    ``sample_x``: one batched input (shape/dtype only) — flatten-boundary
    leaf mappings depend on the feature-map dims."""
    from fedml_tpu.models.cnn import CNNDropOut, CNNOriginalFedAvg
    from fedml_tpu.models.resnet import CifarResNet

    policy = LayoutPolicy(lane=lane, sublane=sublane, lane_snap=lane_snap)
    overrides: Dict[str, Tuple[Callable, Callable]] = {}
    if isinstance(model, CifarResNet):
        twin = _cifar_resnet_twin(model, policy)
    elif isinstance(model, CNNOriginalFedAvg):
        twin = _cnn_original_twin(model, policy, sample_x)
    elif isinstance(model, CNNDropOut):
        raise NotImplementedError(
            "compute_layout cannot pad dropout-bearing models: the mask "
            "draw shapes follow the PHYSICAL layout, so padded-vs-logical "
            "exactness is unattainable by construction (CNNDropOut; use "
            "CNNOriginalFedAvg or a GroupNorm conv net)")
    else:
        raise NotImplementedError(
            f"compute_layout has no physical-twin builder for "
            f"{type(model).__name__}; supported: CifarResNet (gn/bn/none"
            "), CNNOriginalFedAvg")
    if isinstance(twin, tuple):
        twin, overrides = twin
    layout = ComputeLayout(logical_model=model, physical_model=twin,
                           overrides=overrides)
    layout._build_specs(sample_x)
    return layout


def step_dtype_model(model, dtype):
    """COMPUTE-dtype twin for the bf16 client step
    (``cfg.client_step_dtype="bf16"``): a clone of ``model`` whose
    layers compute in ``dtype`` while the PARAM TREE stays float32
    (flax's ``dtype=`` casts inputs and params at each layer's compute;
    ``param_dtype`` is untouched) — so the jitted client step's matmuls
    run at bf16 MXU rate while gradients, the optimizer update, the
    aggregation, and the server carry all stay fp32. The param tree is
    structurally identical to the logical model's, so everything above
    the client step (checkpoints, the wire, robust aggregators, the
    compute-layout pad/unpad) is untouched.

    Requires the model family to expose a ``dtype`` compute field
    (CifarResNet, CNNOriginalFedAvg/CNNDropOut, LogisticRegression);
    refuses loudly otherwise — silently training fp32 under a bf16 flag
    is exactly the drift the loud-refusal convention exists for."""
    fields = getattr(type(model), "__dataclass_fields__", {})
    if "dtype" not in fields:
        raise NotImplementedError(
            f"client_step_dtype: {type(model).__name__} has no compute-"
            "dtype field; supported families expose `dtype` "
            "(CifarResNet, CNNOriginalFedAvg, CNNDropOut, "
            "LogisticRegression)")
    return model.clone(dtype=dtype)


def im2col_layout(model, sample_x):
    """Conv lane shaping beyond s2d (docs/EXECUTION.md "Client-step levers"):
    a :class:`ComputeLayout` whose physical twin rephrases the 5x5 STEM
    conv as patch extraction + a 1x1 conv — the MXU contraction dim
    grows from Cin (1, or 4 under s2d) to k²·Cin (25/100), one dense
    GEMM instead of a thin-channel conv. Algebraically the same dot per
    output position (the kernel mapping is a pure transpose+reshape in
    ``conv_general_dilated_patches``'s (c, kh, kw) channel order, exact
    both ways); XLA may associate the 25-element reduction differently
    than the conv lowering, so the step carries the CNN family's
    documented ~1-ulp tolerance rather than the ResNet family's
    bit-exactness. Widths are NOT padded here — compose measurement-wise
    with ``compute_layout`` by measuring both, not structurally.

    Supported: ``CNNOriginalFedAvg`` (stem "conv" or "s2d"). Dropout
    models refuse for the usual mask-shape reason; other families have
    no 5x5 stem to rephrase."""
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    if not isinstance(model, CNNOriginalFedAvg):
        raise NotImplementedError(
            f"im2col_layout has no stem-rephrasing twin for "
            f"{type(model).__name__}; supported: CNNOriginalFedAvg")
    if model.im2col:
        raise ValueError("model is already an im2col physical twin")
    c1 = (model.widths or (32, 64))[0]
    cin = 4 if model.stem == "s2d" else 1
    k = 5

    def pad_stem(leaf):  # [5, 5, cin, c1] -> [1, 1, cin*25, c1]
        return jnp.transpose(leaf, (2, 0, 1, 3)).reshape(
            1, 1, cin * k * k, c1)

    def unpad_stem(leaf):
        return jnp.transpose(
            leaf.reshape(cin, k, k, c1), (1, 2, 0, 3))

    twin = model.clone(im2col=True)
    layout = ComputeLayout(
        logical_model=model, physical_model=twin,
        overrides={".params/Conv_0/kernel": (pad_stem, unpad_stem)})
    layout._build_specs(sample_x)
    return layout


def wrap_local_train(local_train, layout: ComputeLayout):
    """Wrap a PHYSICAL-model local trainer into the logical-shape
    contract: ``wrapped(net_logical, x, y, mask, rng) -> (net_logical',
    loss)``. Pad-on-entry, slice-on-exit — the only place physical
    shapes exist; everything above (aggregation, robust aggregators,
    carry protocol, checkpoints, the wire) keeps seeing logical
    shapes."""

    def wrapped(net, x, y, mask, rng):
        phys, loss = local_train(layout.pad(net), x, y, mask, rng)
        return layout.unpad(phys), loss

    return wrapped
