"""Plain reference of Granite 4.0-H in the federated adapter round: the layer
equations in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no chunks, no vmap
over clients. It imports nothing from the model
(``models/granite_hybrid.py``); it reads the same parameter names, so the
trees a model initialised are arguments here: ``base`` (the frozen
parameters, in whatever dtype the program holds them: each is widened to
float32 where it is used, a layer at a time, so that a 6.4 GB bfloat16 base
never becomes a 12.8 GB float32 one) and ``adapters`` (the ``lora_*`` pairs,
the only parameters the loss is differentiated by). Both keep a period's
layers stacked along a leading axis (``periods/layer_<j>/...``).

Source of the sizes: https://huggingface.co/ibm-granite/granite-4.0-h-micro/
blob/main/config.json; ``cfg`` is a dict of its keys plus ``adapter_rank``,
``adapter_alpha`` and, for the runner, ``base`` (the frozen tree) and
``token_block``. Equations, with ``RMS(x; w) = w x / sqrt(mean x^2 + eps)``:

- Every linear projection: ``x W + (alpha / r) (x A) B``; no bias.
- ``h = embedding_multiplier E[ids]``; layer ``l``:
  ``h = h + residual_multiplier Mixer_l(RMS(h))``, then
  ``h = h + residual_multiplier MLP(RMS(h))``.
- MLP: ``[a | b] = x W_in``, ``(silu(a) b) W_out``.
- Attention (``layer_types[l] == "attention"``): ``q, k, v`` by heads, NO
  positions, DENSE causal softmax of ``q k^T attention_multiplier``, a
  key-value head serving ``H_q / H_kv`` query heads, then ``W_o``.
- Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC`` through a causal depthwise
  convolution with bias and SiLU; ``xBC -> x' [H, P], B [G, N], C [G, N]``;
  ``Delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head, TOKEN BY
  TOKEN: ``S = exp(Delta_t A) S + Delta_t x'_t B_t^T``,
  ``y_t = S C_t + D x'_t``; ``RMS(y silu(z); w)`` over all the inner
  channels (gate first, one group), then ``W_out``.
- ``logits = RMS(h) E^T / logits_scaling`` (tied head); mean cross-entropy
  over the tokens whose label is not ``pad_id``, a sequence at a time.

``fedavg_round`` is one FedAvg round over the ADAPTERS: clients in turn,
``epochs`` passes of plain SGD over their batches, the sample-weighted mean of
their adapters.

So that the published widths fit one chip beside the program they are
compared with, ``cfg["token_block"]`` (unset in the CPU tests' sizes)
computes the same sums a block of tokens at a time: the recurrence's tokens in
blocks whose states alone are kept for the backward pass, the attention's
queries and the head's tokens in blocks, and a layer's activations computed
again in the backward pass (``jax.checkpoint``). No equation changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: ``None`` computes every product as written (float32). The benchmark's
#: lower-precision reading sets a number of significand bits: both operands
#: of every matrix product are then rounded to it first (the gradient passes
#: straight through the rounding), which is how a chip with narrower
#: multipliers would compute, and what the comparison's limits must catch
#: (8 is bfloat16's, 4 float8 e4m3's). Read while a function is TRACED: set
#: it before ``loss_and_grad`` builds the function that is to use it.
PRODUCT_BITS = None


def _operand(x):
    x = x.astype(F32)
    if PRODUCT_BITS is None:
        return x
    drop = 24 - PRODUCT_BITS        # float32 keeps 24 significand bits
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    rounded = jax.lax.bitcast_convert_type(bits, F32)
    return x + jax.lax.stop_gradient(rounded - x)


def dot(a, b):
    return _operand(a) @ _operand(b)


def einsum(spec, a, b):
    return jnp.einsum(spec, _operand(a), _operand(b))


def rms(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def silu(x):
    return x * jax.nn.sigmoid(x)


def linear(base, adapters, name, x, cfg):
    """``x W + (alpha / r) (x A) B``."""
    y = dot(x, base[name])
    if f"lora_{name}_a" not in adapters:
        return y
    low = dot(dot(x, adapters[f"lora_{name}_a"]), adapters[f"lora_{name}_b"])
    return y + (cfg["adapter_alpha"] / cfg["adapter_rank"]) * low


def recurrence(x, delta, a, b, c, block=None):
    """``x [T, H, P]``, ``delta [T, H]``, ``a [H]``, ``b, c [T, H, N]`` ->
    ``y [T, H, P]``: the state-space recurrence, one token after another."""

    def token(state, inputs):
        x_t, d_t, b_t, c_t = inputs
        state = jnp.exp(d_t * a)[:, None, None] * state + einsum(
            "hp,hn->hpn", d_t[:, None] * x_t, b_t)
        return state, einsum("hpn,hn->hp", state, c_t)

    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), F32)
    tokens = (x, delta, b, c)
    if not block or x.shape[0] % block:
        return jax.lax.scan(token, zero, tokens)[1]
    blocks = tuple(v.reshape((-1, block) + v.shape[1:]) for v in tokens)
    out = jax.lax.scan(
        jax.checkpoint(lambda state, blk: jax.lax.scan(token, state, blk)),
        zero, blocks)[1]
    return out.reshape((-1,) + out.shape[2:])


def mamba2(base, adapters, x, cfg):
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner, taps, t = h * p, cfg["mamba_d_conv"], x.shape[0]
    conv_dim = inner + 2 * g * n
    zxbcdt = linear(base, adapters, "in_proj", x, cfg)
    z, conv_in, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                      zxbcdt[:, inner + conv_dim:])
    conv = jnp.zeros_like(conv_in) + base["conv_bias"].astype(F32)
    for j in range(taps):           # tap j looks taps - 1 - j tokens back
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, conv_dim)), conv_in[:t - back]])
        conv = conv + shifted * base["conv_weight"][j].astype(F32)
    conv = silu(conv)
    xs = conv[:, :inner].reshape(t, h, p)
    b = jnp.repeat(conv[:, inner:inner + g * n].reshape(t, g, n), h // g, 1)
    c = jnp.repeat(conv[:, inner + g * n:].reshape(t, g, n), h // g, 1)
    delta = jax.nn.softplus(dt + base["dt_bias"].astype(F32))
    y = recurrence(xs, delta, -jnp.exp(base["A_log"].astype(F32)), b, c,
                   cfg.get("token_block"))
    y = y + base["D"].astype(F32)[:, None] * xs
    y = rms(y.reshape(t, inner) * silu(z), base["norm_weight"],
            cfg["rms_norm_eps"])
    return linear(base, adapters, "out_proj", y, cfg)


def attention(base, adapters, x, cfg):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, t = cfg["hidden_size"] // hq, x.shape[0]
    q = linear(base, adapters, "q_proj", x, cfg).reshape(t, hq, hd)
    k = linear(base, adapters, "k_proj", x, cfg).reshape(t, hkv, hd)
    v = linear(base, adapters, "v_proj", x, cfg).reshape(t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)

    def attend(q_rows, first):
        """Queries ``first ..`` against every key, the later ones masked."""
        scores = einsum("qhd,khd->hqk", q_rows, k) \
            * cfg["attention_multiplier"]
        seen = (first + np.arange(q_rows.shape[0]))[:, None] \
            >= np.arange(t)[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    block = cfg.get("token_block") or t
    o = jnp.concatenate([
        jax.checkpoint(attend, static_argnums=1)(q[i:i + block], i)
        for i in range(0, t, block)])
    return linear(base, adapters, "o_proj", o.reshape(t, hq * hd), cfg)


def mlp(base, adapters, x, cfg):
    f = cfg["shared_intermediate_size"]
    ab = linear(base, adapters, "input_linear", x, cfg)
    return linear(base, adapters, "output_linear",
                  silu(ab[:, :f]) * ab[:, f:], cfg)


def layer(base, adapters, x, cfg, kind: str):
    eps, scale = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = attention if kind == "attention" else mamba2
    x = x + scale * mixer(base["mixer"], adapters.get("mixer", {}),
                          rms(x, base["input_norm"], eps), cfg)
    return x + scale * mlp(base["mlp"], adapters.get("mlp", {}),
                           rms(x, base["post_norm"], eps), cfg)


def _period(kinds) -> int:
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and list(kinds) == list(kinds[:p]) * (
                len(kinds) // p):
            return p
    return len(kinds)


def init_base(cfg, seed: int):
    """The frozen tree made from ``seed`` by the laws the configuration file
    lists under ``assumed``, in ``cfg["base_dtype"]`` (bfloat16), a tensor
    at a time on the default device. It is what the benchmark hands BOTH the
    program (``base_params``) and this file, so that neither side's weights
    are the other's: layer ``l`` is drawn under its own index, as slice
    ``l // period`` of ``periods/layer_<l % period>``, where
    :func:`hidden_states` reads it. ``seed`` is any whole number."""
    dtype = jnp.dtype(cfg.get("base_dtype", "bfloat16"))
    d, f, v = (cfg["hidden_size"], cfg["shared_intermediate_size"],
               cfg["vocab_size"])
    h, p, n, g, taps = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                        cfg["mamba_d_state"], cfg["mamba_n_groups"],
                        cfg["mamba_d_conv"])
    inner, conv_dim = h * p, h * p + 2 * g * n
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    bound = taps ** -0.5            # the convolution's framework default

    def inverse_softplus(y):
        return y + jnp.log(-jnp.expm1(-y))

    laws = {
        "normal": lambda k, s: 0.02 * jax.random.normal(k, s, F32),
        "ones": lambda k, s: jnp.ones(s, F32),
        "conv": lambda k, s: jax.random.uniform(k, s, F32, -bound, bound),
        # A = -exp(A_log) with exp(A_log) uniform in [1, 16]
        "A_log": lambda k, s: jnp.log(jax.random.uniform(k, s, F32, 1., 16.)),
        # softplus(dt_bias) log-uniform in [0.001, 0.1]
        "dt_bias": lambda k, s: inverse_softplus(jnp.exp(jax.random.uniform(
            k, s, F32, np.log(1e-3), np.log(1e-1)))),
    }
    # a tensor of every period at once, stacked as it is drawn: no second copy
    draw = jax.jit(lambda law, keys, shape: jax.vmap(
        lambda k: laws[law](k, shape))(keys).astype(dtype),
        static_argnums=(0, 2))
    mlp_of = {"input_linear": ("normal", (d, 2 * f)),
              "output_linear": ("normal", (f, d))}
    mixer_of = {
        "mamba": {"in_proj": ("normal", (d, inner + conv_dim + h)),
                  "conv_weight": ("conv", (taps, conv_dim)),
                  "conv_bias": ("conv", (conv_dim,)),
                  "A_log": ("A_log", (h,)), "dt_bias": ("dt_bias", (h,)),
                  "D": ("ones", (h,)), "norm_weight": ("ones", (inner,)),
                  "out_proj": ("normal", (inner, d))},
        "attention": {"q_proj": ("normal", (d, hq * hd)),
                      "k_proj": ("normal", (d, hkv * hd)),
                      "v_proj": ("normal", (d, hkv * hd)),
                      "o_proj": ("normal", (hq * hd, d))},
    }
    root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 0xBA5E)
    fold = jax.vmap(jax.random.fold_in, in_axes=(0, None))

    def drawn(keys, tensors):
        return {name: draw(law, fold(keys, i), shape)
                for i, (name, (law, shape)) in enumerate(
                    sorted(tensors.items()))}

    kinds = list(cfg["layer_types"])
    period = _period(kinds)

    def layers(j):      # layers j, j + period, ...: one a period
        keys = jnp.stack([jax.random.fold_in(root, l)
                          for l in range(j, len(kinds), period)])
        return {"input_norm": draw("ones", keys, (d,)),
                "post_norm": draw("ones", keys, (d,)),
                "mixer": drawn(fold(keys, 0), mixer_of[kinds[j]]),
                "mlp": drawn(fold(keys, 1), mlp_of)}

    last = jax.random.fold_in(root, len(kinds))[None]
    return {"embed": draw("normal", last, (v, d))[0],
            "final_norm": draw("ones", last, (d,))[0],
            "periods": {f"layer_{j}": layers(j) for j in range(period)}}


def hidden_states(base, adapters, ids, cfg):
    """``ids [T]`` -> the residual stream after the last layer ``[T, d]``.
    Layer ``l`` is slice ``l // period`` of ``periods/layer_<l % period>``:
    the periods one after another (``lax.scan`` over the stacked slices, so
    that one period is traced and one period's float32 weights are alive),
    a period's layers written out."""
    x = cfg["embedding_multiplier"] * base["embed"][ids].astype(F32)
    kinds = list(cfg["layer_types"])
    period = _period(kinds)

    def one_period(x, stacked):
        b, a = stacked
        for j, kind in enumerate(kinds[:period]):
            # A function of its own every time this is traced:
            # ``jax.checkpoint`` keeps the trace of a function it has seen,
            # and would hand a second ``PRODUCT_BITS`` the first's products.
            def run(b_j, a_j, x, kind=kind):
                return layer(b_j, a_j, x, cfg, kind)

            if cfg.get("token_block"):
                run = jax.checkpoint(run)
            x = run(b[f"layer_{j}"], a.get(f"layer_{j}", {}), x)
        return x, None

    return jax.lax.scan(one_period, x, (
        base["periods"], adapters.get("periods", {})))[0]


def token_losses(base, x, labels, cfg, pad_id: int = 0):
    """``(sum of the real tokens' cross-entropies, their number)`` from the
    residual stream ``x [T, d]``: the tied head, a block of tokens at a time
    where ``token_block`` says so."""
    h = rms(x, base["final_norm"], cfg["rms_norm_eps"])

    def block_loss(h_rows, y_rows):
        z = dot(h_rows, base["embed"].T) / cfg["logits_scaling"]
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, y_rows[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * (y_rows != pad_id).astype(F32))

    block = cfg.get("token_block") or h.shape[0]
    total = sum(jax.checkpoint(block_loss)(h[i:i + block], labels[i:i + block])
                for i in range(0, h.shape[0], block))
    return total, jnp.sum((labels != pad_id).astype(F32))


def logits(base, adapters, ids, cfg):
    x = hidden_states(base, adapters, ids, cfg)
    return dot(rms(x, base["final_norm"], cfg["rms_norm_eps"]),
               base["embed"].T) / cfg["logits_scaling"]


def sequence_loss(adapters, base, ids, labels, cfg, pad_id: int = 0):
    """Mean cross-entropy of one sequence over its non-pad labels."""
    total, real = token_losses(
        base, hidden_states(base, adapters, ids, cfg), labels, cfg, pad_id)
    return total / jnp.maximum(real, 1.0)


def batch_loss(adapters, base, ids, labels, cfg, pad_id: int = 0):
    """``ids, labels [B, T]``: the mean over the batch's sequences."""
    losses = [sequence_loss(adapters, base, ids[b], labels[b], cfg, pad_id)
              for b in range(ids.shape[0])]
    return sum(losses) / len(losses)


def loss_and_grad(cfg, pad_id: int = 0):
    """``(adapters, ids [B, T], labels [B, T]) -> (loss, gradients)`` with
    respect to the adapters, jitted once for every client and step that uses
    it. ``cfg["base"]`` is the frozen tree: an operand of the jitted
    function, not its constant."""
    base = cfg["base"]
    sizes = {k: v for k, v in cfg.items() if k != "base"}
    fn = jax.jit(jax.value_and_grad(
        lambda a, b, x, y: batch_loss(a, b, x, y, sizes, pad_id)))
    return lambda adapters, ids, labels: fn(adapters, base, ids, labels)


def client_update(adapters, batches, cfg, lr: float, epochs: int = 1,
                  pad_id: int = 0, grad=None):
    """Plain SGD over ``batches`` (a list of ``(ids [B, T], labels [B, T])``)
    in order, ``epochs`` times. Returns ``(adapters', mean loss)`` with the
    loss averaged over a pass's batches, then over the passes."""
    grad = grad or loss_and_grad(cfg, pad_id)
    epoch_losses = []
    for _ in range(epochs):
        losses = []
        for ids, labels in batches:
            loss, g = grad(adapters, ids, labels)
            adapters = jax.tree.map(lambda w, dw: w - lr * dw, adapters, g)
            losses.append(float(loss))
        epoch_losses.append(np.mean(losses))
    return adapters, float(np.mean(epoch_losses))


def fedavg_round(adapters, clients, cfg, lr: float, epochs: int = 1,
                 pad_id: int = 0):
    """``clients``: a list of ``(batches, n_samples)``. Returns the
    sample-weighted mean of the clients' trained adapters and of their
    losses."""
    total = float(sum(n for _, n in clients))
    mean, loss = None, 0.0
    with jax.default_matmul_precision("highest"):
        grad = loss_and_grad(cfg, pad_id)
        for batches, n in clients:
            trained, client_loss = client_update(adapters, batches, cfg, lr,
                                                 epochs, pad_id, grad)
            share = jax.tree.map(lambda w: (n / total) * w, trained)
            mean = share if mean is None else jax.tree.map(jnp.add, mean,
                                                           share)
            loss += (n / total) * client_loss
    return mean, loss
