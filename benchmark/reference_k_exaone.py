"""Plain reference of K-EXAONE (``exaone_moe``) in the federated adapter
round: the layer equations in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no slabs, no vmap
over clients. It imports nothing from the model (``models/k_exaone.py``); it
reads the same parameter names, so the trees a model initialised are
arguments here: ``base`` (the frozen parameters, in whatever dtype the
program holds them: each is widened to float32 where it is used, a layer or
an expert at a time) and ``adapters`` (the ``lora_*`` pairs, the only
parameters the loss is differentiated by).

Source of the sizes: https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/
blob/main/config.json; ``cfg`` is a dict of its keys plus
``num_experts_held``, ``first_expert_held``, ``adapter_rank``,
``adapter_alpha`` and, for the runner, ``base`` (the frozen tree) and
``token_block``. Equations, with ``RMS(x; w) = w x / sqrt(mean x^2 + eps)``:

- Every linear projection: ``x W + (alpha / r) (x A) B``; no bias.
- ``h = E[ids]``; layer ``l``: ``h = h + RMS(Attn_l(h))``, then
  ``h = h + RMS(FFN_l(h))``: the norm on each branch's OUTPUT, none on its
  input (EXAONE 4.0's block). After the last layer ``RMS``, then the head.
- Attention: ``q = RMS_head(W_q x)``, ``k = RMS_head(W_k x)`` (a norm over
  each head's ``head_dim``), ``v = W_v x``; a key-value head serves
  ``H_q / H_kv`` query heads; DENSE masked softmax of ``q k^T head_dim^-0.5``.
  ``layer_types[l] == "sliding_attention"``: rotary positions on the whole
  head (``rope_theta``) and query ``i`` sees key ``j`` iff ``0 <= i - j <
  sliding_window``; ``"full_attention"``: NO positions, causal.
- FFN, ``mlp_layer_types[l] == "dense"``: ``W_down (silu(W_gate h) W_up h)``.
- FFN, ``"sparse"``: ``s = sigmoid(W_r h)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + b``; weights
  ``routed_scaling_factor s[idx] / (sum s[idx] + 1e-20)``; ``sum_{e in idx, e
  held} w_e E_e(h) + E_shared(h)``, every ``E`` a gated MLP, every held
  expert computed over EVERY token and masked by the routing. Experts
  outside ``first_expert_held .. + num_experts_held - 1`` add nothing.
- ``logits = RMS(h) W_head`` (untied); mean cross-entropy over the tokens
  whose label is not ``pad_id``, a sequence at a time.

``init_base`` draws the frozen tree from a seed; ``balance_router`` then sets
the routers' selection biases by the balancing rule that router was trained
with, on the seed's own tokens, so that the drawn router spreads its tokens
as a trained one does.

``fedavg_round`` is one FedAvg round over the ADAPTERS: clients in turn,
``epochs`` passes of plain SGD over their batches, the sample-weighted mean of
their adapters.

So that the published widths fit one chip beside the base they are compared
on, ``cfg["token_block"]`` (unset in the CPU tests' sizes) computes the same
sums a block of tokens at a time (the attention's queries, the head's
tokens), a layer's activations again in the backward pass
(``jax.checkpoint``), and an expert's over every token again there too. No
equation changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: ``None`` computes every product as written (float32). The benchmark's
#: lower-precision reading sets a number of significand bits: both operands
#: of every matrix product are then rounded to it first (the gradient passes
#: straight through the rounding): 8 is bfloat16's, 4 float8 e4m3's. Read
#: while a function is TRACED: set it before ``loss_and_grad`` builds the
#: function that is to use it. The router's scores are products too.
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


def _scale(cfg):
    return cfg["adapter_alpha"] / cfg["adapter_rank"]


def linear(base, adapters, name, x, cfg):
    """``x W + (alpha / r) (x A) B``."""
    y = dot(x, base[name])
    if f"lora_{name}_a" not in adapters:
        return y
    low = dot(dot(x, adapters[f"lora_{name}_a"]), adapters[f"lora_{name}_b"])
    return y + _scale(cfg) * low


def rotate(x, theta):
    """Rotary positions ``0 .. T - 1`` on ``x [T, H, D]``: channel ``i`` and
    channel ``i + D / 2`` turn together by ``t theta^(-2 i / D)``."""
    t, _, d = x.shape
    half = d // 2
    angle = np.arange(t)[:, None] * float(theta) ** (
        -np.arange(half)[None, :] / half)                     # [T, D / 2]
    cos, sin = (jnp.asarray(f(angle), F32)[:, None, :]
                for f in (np.cos, np.sin))
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(base, adapters, x, cfg, window: int):
    """``window`` 0: causal, no positions."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    q = linear(base, adapters, "q_proj", x, cfg).reshape(t, hq, hd)
    k = linear(base, adapters, "k_proj", x, cfg).reshape(t, hkv, hd)
    v = linear(base, adapters, "v_proj", x, cfg).reshape(t, hkv, hd)
    q, k = rms(q, base["q_norm"], eps), rms(k, base["k_norm"], eps)
    if window:
        theta = cfg.get("rope_theta") or cfg["rope_parameters"]["rope_theta"]
        q, k = rotate(q, theta), rotate(k, theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)

    def attend(q_rows, first):
        """Queries ``first ..`` against every key, the unseen ones masked."""
        scores = einsum("qhd,khd->hqk", q_rows, k) * hd ** -0.5
        back = (first + np.arange(q_rows.shape[0]))[:, None] \
            - np.arange(t)[None, :]
        seen = (back >= 0) & (back < (window or t))
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    block = cfg.get("token_block") or t
    o = jnp.concatenate([
        jax.checkpoint(attend, static_argnums=1)(q[i:i + block], i)
        for i in range(0, t, block)])
    return linear(base, adapters, "o_proj", o.reshape(t, hq * hd), cfg)


def gated_mlp(base, adapters, x, cfg):
    gate = linear(base, adapters, "gate_proj", x, cfg)
    up = linear(base, adapters, "up_proj", x, cfg)
    return linear(base, adapters, "down_proj", silu(gate) * up, cfg)


def routing(base, x, cfg):
    """``(idx [T, k], weight [T, k])`` over ALL ``num_experts``."""
    scores = jax.nn.sigmoid(dot(x, base["router"]))
    k = cfg["num_experts_per_tok"]
    _, idx = jax.lax.top_k(scores + base["router_bias"].astype(F32), k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return idx, cfg["routed_scaling_factor"] * weight


def sparse_moe(base, adapters, x, cfg):
    """The held experts' part of the routed sum, and the shared expert."""
    idx, weight = routing(base, x, cfg)
    first = cfg.get("first_expert_held", 0)
    held = base["experts_gate_up"].shape[0]
    f = base["experts_down"].shape[1]
    names = ("gate_a", "gate_b", "up_a", "up_b", "down_a", "down_b")
    pairs = tuple(adapters[f"lora_experts_{n}"] for n in names) \
        if "lora_experts_gate_a" in adapters else None

    def expert(acc, stacked):
        e, w_gate_up, w_down, pair = stacked
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        gate_up = dot(x, w_gate_up)
        gate, up = gate_up[:, :f], gate_up[:, f:]
        if pair is not None:
            gate = gate + _scale(cfg) * dot(dot(x, pair[0]), pair[1])
            up = up + _scale(cfg) * dot(dot(x, pair[2]), pair[3])
        hidden = silu(gate) * up
        out = dot(hidden, w_down)
        if pair is not None:
            out = out + _scale(cfg) * dot(dot(hidden, pair[4]), pair[5])
        return acc + w_e[:, None] * out, None

    if cfg.get("token_block"):
        expert = jax.checkpoint(expert)
    routed = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(held), base["experts_gate_up"], base["experts_down"],
        pairs))[0]
    return routed + gated_mlp(base["shared"], adapters.get("shared", {}), x,
                              cfg)


def layer(base, adapters, x, cfg, window: int, sparse: bool):
    eps = cfg["rms_norm_eps"]
    x = x + rms(attention(base["attn"], adapters.get("attn", {}), x, cfg,
                          window), base["post_attn_norm"], eps)
    if sparse:
        branch = sparse_moe(base["moe"], adapters.get("moe", {}), x, cfg)
    else:
        branch = gated_mlp(base["mlp"], adapters.get("mlp", {}), x, cfg)
    return x + rms(branch, base["post_ffn_norm"], eps)


def _kinds(cfg):
    """``[(window or 0, sparse)]`` of the layers that are held."""
    n = cfg["num_hidden_layers"]
    return [(cfg["sliding_window"] if kind == "sliding_attention" else 0,
             mlp == "sparse")
            for kind, mlp in zip(cfg["layer_types"][:n],
                                 cfg["mlp_layer_types"][:n])]


def hidden_states(base, adapters, ids, cfg):
    """``ids [T]`` -> the residual stream after the last layer ``[T, d]``."""
    x = base["embed"][ids].astype(F32)
    for l, (window, sparse) in enumerate(_kinds(cfg)):
        # A function of its own every time this is traced: ``jax.checkpoint``
        # keeps the trace of a function it has seen, and would hand a second
        # ``PRODUCT_BITS`` the first's products.
        def run(b, a, x, window=window, sparse=sparse):
            return layer(b, a, x, cfg, window, sparse)

        if cfg.get("token_block"):
            run = jax.checkpoint(run)
        x = run(base[f"layer_{l}"], adapters.get(f"layer_{l}", {}), x)
    return x


def token_losses(base, x, labels, cfg, pad_id: int = 0):
    """``(sum of the real tokens' cross-entropies, their number)`` from the
    residual stream ``x [T, d]``, a block of tokens at a time where
    ``token_block`` says so."""
    h = rms(x, base["final_norm"], cfg["rms_norm_eps"])

    def block_loss(h_rows, y_rows):
        z = dot(h_rows, base["lm_head"])
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
               base["lm_head"])


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


def init_base(cfg, seed: int):
    """The frozen tree made from ``seed`` by the laws the configuration file
    lists under ``assumed``: every matrix and the router normal(0, 0.02);
    the embedding normal(0, 1) and the norms on the branches' outputs 0.05
    (the other norms 1), so that a token's own row leads the residual
    stream as in a trained model (at random weights a query averages its
    window and the branches' outputs are nearly one vector for every token
    of a sequence); the head normal(0, 0.002), so that the first logits are
    small and the first loss is the prior's; the router's selection bias
    normal(0, 0.05)
    until :func:`balance_router` sets it. Drawn in float32 and then narrowed to
    ``cfg["base_dtype"]`` (bfloat16), a tensor at a time on the default
    device. It is what the benchmark hands BOTH the program (``base_params``)
    and this file, so that neither side's weights are the other's. ``seed``
    is any whole number."""
    dtype = jnp.dtype(cfg.get("base_dtype", "bfloat16"))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, experts = cfg["num_experts_held"], cfg["num_experts"]
    draw = jax.jit(lambda key, std, shape: (
        std * jax.random.normal(key, shape, F32)).astype(dtype),
        static_argnums=2)
    root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 0xBA5E)

    def drawn(key, tensors):
        return {name: (jnp.full(shape, BRANCH_NORM if name.startswith(
            "post_") else 1.0, dtype) if std is None
                       else draw(jax.random.fold_in(key, i), std, shape))
                for i, (name, (std, shape)) in enumerate(
                    sorted(tensors.items()))}

    def mlp(width):
        return {"gate_proj": (0.02, (d, width)), "up_proj": (0.02, (d, width)),
                "down_proj": (0.02, (width, d))}

    attn = {"q_proj": (0.02, (d, hq * hd)), "k_proj": (0.02, (d, hkv * hd)),
            "v_proj": (0.02, (d, hkv * hd)), "o_proj": (0.02, (hq * hd, d)),
            "q_norm": (None, (hd,)), "k_norm": (None, (hd,))}
    moe = {"router": (0.02, (d, experts)),
           "router_bias": (ROUTER_BIAS_STD, (experts,)),
           "experts_gate_up": (0.02, (held, d, 2 * fe)),
           "experts_down": (0.02, (held, fe, d))}
    tree = {}
    for l, (_, sparse) in enumerate(_kinds(cfg)):
        key = jax.random.fold_in(root, l)
        one = drawn(key, {"post_attn_norm": (None, (d,)),
                          "post_ffn_norm": (None, (d,))})
        one["attn"] = drawn(jax.random.fold_in(key, 0), attn)
        if sparse:
            one["moe"] = drawn(jax.random.fold_in(key, 1), moe)
            one["moe"]["shared"] = drawn(jax.random.fold_in(key, 2), mlp(fe))
        else:
            one["mlp"] = drawn(jax.random.fold_in(key, 1), mlp(f))
        tree[f"layer_{l}"] = one
    last = jax.random.fold_in(root, cfg["num_hidden_layers"])
    tree.update(drawn(last, {"embed": (EMBED_STD, (v, d)),
                             "final_norm": (None, (d,)),
                             "lm_head": (HEAD_STD, (d, v))}))
    return tree


#: ``init_base``'s laws that are not normal(0, 0.02) or 1 (its docstring)
EMBED_STD, HEAD_STD, BRANCH_NORM, ROUTER_BIAS_STD = 1.0, 0.002, 0.05, 0.05
#: steps and first step size of :func:`balance_router`'s rule; the step
#: falls linearly to nothing, so the bias settles
BALANCE_STEPS, BALANCE_RATE = 400, 0.02


def balance_router(base, cfg, ids, steps: int = BALANCE_STEPS,
                   rate: float = BALANCE_RATE):
    """``base`` with every sparse layer's ``router_bias`` set by the
    auxiliary-loss-free balancing rule of the router this configuration
    carries (DeepSeek-V3's, arXiv:2408.15664): from 0, ``b_e += u sign(mean
    load - load_e)`` over the tokens ``ids [S, T]``, layer after layer (a
    layer's input depends on the biases before it), the load counted over
    ALL ``num_experts``. Returns ``(base', [fullest expert's load over the
    mean a sparse layer, before and after])``.

    Why the benchmark needs it: a router drawn at random is not balanced as
    a trained one is. The branches' outputs are normalised to unit scale and
    at random weights are nearly the same vector for every token, so every
    token would choose the same experts: measured on the chip, a held expert
    drew 0 or 1,500 of a client-step's 4,096 tokens where the mean is 256
    (PERF.md section 6, PR 34), and whether a seed's favourite experts are
    among the held ones would decide how long its rounds take and how far a
    single flipped choice moves the update. The bias SELECTS only; it is a
    frozen tensor of the base like any other and both sides read the same.
    Plain float32 products at the backend's default precision: what is
    wanted is a balance, not a comparison."""
    ids = jnp.asarray(ids)
    sizes = {k: v for k, v in cfg.items() if k != "base"}
    k, experts = sizes["num_experts_per_tok"], sizes["num_experts"]
    eps = sizes["rms_norm_eps"]

    @jax.jit
    def settle(scores):
        target = scores.shape[0] * k / experts

        def load_of(bias):
            _, idx = jax.lax.top_k(scores + bias, k)
            return jnp.zeros(experts, F32).at[idx.reshape(-1)].add(1.0)

        def step(i, bias):
            return bias + rate * (1 - i / steps) * jnp.sign(
                target - load_of(bias))

        bias = jax.lax.fori_loop(0, steps, step, jnp.zeros(experts, F32))
        return bias, jnp.max(load_of(0.0)) / target, jnp.max(
            load_of(bias)) / target

    base = dict(base)
    hidden = [base["embed"][row].astype(F32) for row in ids]
    found = []
    for l, (window, sparse) in enumerate(_kinds(sizes)):
        one = base[f"layer_{l}"]
        mix = jax.jit(lambda b, x, window=window: x + rms(attention(
            b["attn"], {}, x, sizes, window), b["post_attn_norm"], eps))
        hidden = [mix(one, x) for x in hidden]
        if sparse:
            scores = jax.jit(lambda b, x: jax.nn.sigmoid(dot(x, b["router"])))
            bias, before, after = settle(jnp.concatenate(
                [scores(one["moe"], x) for x in hidden]))
            found.append((float(before), float(after)))
            one = dict(one, moe=dict(one["moe"], router_bias=bias.astype(
                one["moe"]["router_bias"].dtype)))
            base[f"layer_{l}"] = one
            feed = jax.jit(lambda b, x: sparse_moe(b["moe"], {}, x, sizes))
        else:
            feed = jax.jit(lambda b, x: gated_mlp(b["mlp"], {}, x, sizes))
        hidden = [x + rms(feed(one, x), one["post_ffn_norm"], eps)
                  for x in hidden]
    return base, found


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
