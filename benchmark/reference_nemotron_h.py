"""Plain reference of Nemotron-H (``nemotron_h``) in the federated adapter
round: the block equations in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no chunked scan, no
grouped product, no vmap over clients. It imports nothing from the model
(``models/nemotron_h.py``) or from ``ops/``; it reads the same parameter
names, so the trees a model initialised are arguments here: ``base`` (the
frozen parameters, in whatever dtype the program holds them: each is widened
to float32 where it is used, a block or an expert at a time) and ``adapters``
(the ``lora_*`` pairs, the only parameters the loss is differentiated by).

Source of the sizes: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-
A3B-BF16/blob/main/config.json; ``cfg`` is a dict of its keys plus
``num_experts_held``, ``first_expert_held``, ``adapter_rank``,
``adapter_alpha`` and, for the runner, ``base`` (the frozen tree) and
``token_block``. Equations, with ``RMS(x; w) = w x / sqrt(mean x^2 + eps)``
and ``d`` the stream's width:

- Every linear map: ``x W + (alpha / r) (x A) B``; no bias.
- ``h = E[ids]``; block ``l`` of kind ``hybrid_override_pattern[l]``:
  ``h = h + Mixer_l(RMS(h))``, ONE mixer a block. After the last block
  ``RMS``, then the untied head.
- ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))``
  (causal, depthwise, ``conv_kernel`` taps, with bias); ``x`` is
  ``mamba_num_heads`` heads of ``mamba_head_dim``, ``B`` and ``C`` ``n_groups``
  groups of ``ssm_state_size`` (head ``h`` reads group ``h // (heads /
  groups)``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``,
  ONE TOKEN AFTER ANOTHER; ``y = GroupRMS(y silu(z))``: the mean square taken
  over each of the ``n_groups`` runs of ``inner / n_groups`` channels, then
  the weight; out ``y W_out``.
- ``*`` (attention): ``q`` of ``num_attention_heads`` heads of ``head_dim``,
  ``k, v`` of ``num_key_value_heads``; NO positions; DENSE causal softmax of
  ``q k^T head_dim^-0.5``; query head ``n`` reads key-value head ``n // (H_q /
  H_kv)``.
- ``E`` (experts): ``s = sigmoid(u W_r)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b``; weights
  ``routed_scaling_factor s[idx] / (sum s[idx] + 1e-20)``; ``sum_{e in idx, e
  held} w_e E_e(u) + E_shared(u)``, ``E(u) = W_down relu(W_up u)^2`` (no
  gate; the held experts' ``W_up`` lie ``[held, width, d]``, a hidden unit a
  row), every held expert computed over EVERY token and masked by the
  routing. Experts outside ``first_expert_held .. + num_experts_held - 1`` add
  nothing.
- ``logits = RMS(h) W_head``; mean cross-entropy over the tokens whose label
  is not ``pad_id``, a sequence at a time.

``init_base`` draws the frozen tree from a seed; ``balance_router`` then sets
the routers' selection biases by the balancing rule that router was trained
with, on the seed's own tokens. ``fedavg_round`` is one FedAvg round over the
ADAPTERS: clients in turn, ``epochs`` passes of plain SGD over their batches,
the sample-weighted mean of their adapters.

So that the published widths fit one chip beside the base they are compared
on, ``cfg["token_block"]`` (unset in the CPU tests' sizes) computes the same
sums a block of tokens at a time (the attention's queries, the head's tokens,
the recurrence's tokens), a block's activations again in the backward pass
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

#: ``init_base``'s laws that are not normal(0, 0.02) or 1 (its docstring): the
#: embedding, the head, the routers' selection bias as drawn, and a block's
#: OUTPUT map (``out_proj``, ``o_proj``, the experts' ``down``)
EMBED_STD, HEAD_STD, ROUTER_BIAS_STD, OUT_STD = 1.0, 0.002, 0.05, 0.001
#: steps and first step size of :func:`balance_router`'s rule; the step
#: falls linearly to nothing, so the bias settles
BALANCE_STEPS, BALANCE_RATE = 400, 0.02
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


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


def group_rms(x, w, eps, groups: int):
    """``x [T, C]``: the mean square over each of ``groups`` runs of
    ``C / groups`` channels, then the weight of ``C``."""
    t, c = x.shape
    x = x.reshape(t, groups, c // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return w.astype(F32) * x.reshape(t, c)


def silu(x):
    return x * jax.nn.sigmoid(x)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _scale(cfg):
    return cfg["adapter_alpha"] / cfg["adapter_rank"]


def linear(base, adapters, name, x, cfg):
    """``x W + (alpha / r) (x A) B``."""
    y = dot(x, base[name])
    if f"lora_{name}_a" not in adapters:
        return y
    low = dot(dot(x, adapters[f"lora_{name}_a"]), adapters[f"lora_{name}_b"])
    return y + _scale(cfg) * low


def kinds(cfg) -> list:
    """``["mamba" | "attn" | "moe"]`` of the blocks that are held."""
    return [KINDS[ch] for ch in cfg["hybrid_override_pattern"][
        :cfg["num_hidden_layers"]]]


# --- the mixers ---------------------------------------------------------------

def recurrence(x, delta, a, b, c, block=None):
    """``x [T, H, P]``, ``delta [T, H]``, ``a [H]``, ``b, c [T, H, N]`` ->
    ``y [T, H, P]``: the state-space recurrence, one token after another
    (``block``: the same steps, a block's states computed again in the
    backward pass)."""

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


def mamba2(base, adapters, u, cfg):
    h, p, n, g = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"])
    inner, taps, t = h * p, cfg["conv_kernel"], u.shape[0]
    conv_dim = inner + 2 * g * n
    zxbcdt = linear(base, adapters, "in_proj", u, cfg)
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
    # a group's B and C serve its heads / groups heads
    b = jnp.repeat(conv[:, inner:inner + g * n].reshape(t, g, n), h // g, 1)
    c = jnp.repeat(conv[:, inner + g * n:].reshape(t, g, n), h // g, 1)
    delta = jax.nn.softplus(dt + base["dt_bias"].astype(F32))
    # the recurrence in blocks of at most 128 tokens: a block's states are
    # what its backward pass holds (a state is heads x head_dim x state)
    block = cfg.get("token_block")
    y = recurrence(xs, delta, -jnp.exp(base["A_log"].astype(F32)), b, c,
                   min(block, 128) if block else None)
    y = y + base["D"].astype(F32)[:, None] * xs
    y = group_rms(y.reshape(t, inner) * silu(z), base["norm_weight"],
                  cfg["layer_norm_epsilon"], g)
    return linear(base, adapters, "out_proj", y, cfg)


def attention(base, adapters, u, cfg):
    """Causal, no positions."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    t = u.shape[0]
    q = linear(base, adapters, "q_proj", u, cfg).reshape(t, hq, hd)
    k = linear(base, adapters, "k_proj", u, cfg).reshape(t, hkv, hd)
    v = linear(base, adapters, "v_proj", u, cfg).reshape(t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)

    def attend(q_rows, first):
        """Queries ``first ..`` against every key, the later ones masked."""
        scores = einsum("qhd,khd->hqk", q_rows, k) * hd ** -0.5
        seen = (first + np.arange(q_rows.shape[0]))[:, None] \
            >= np.arange(t)[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    block = cfg.get("token_block") or t
    o = jnp.concatenate([
        jax.checkpoint(attend, static_argnums=1)(q[i:i + block], i)
        for i in range(0, t, block)])
    return linear(base, adapters, "o_proj", o.reshape(t, hq * hd), cfg)


def relu2_mlp(base, adapters, u, cfg):
    return linear(base, adapters, "down_proj", relu2(
        linear(base, adapters, "up_proj", u, cfg)), cfg)


def routing(base, u, cfg):
    """``(idx [T, k], weight [T, k])`` over ALL ``n_routed_experts``."""
    scores = jax.nn.sigmoid(dot(u, base["router"]))
    k = cfg["num_experts_per_tok"]
    _, idx = jax.lax.top_k(scores + base["router_bias"].astype(F32), k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return idx, cfg["routed_scaling_factor"] * weight


def sparse_moe(base, adapters, u, cfg):
    """The held experts' part of the routed sum, and the shared expert."""
    idx, weight = routing(base, u, cfg)
    first = cfg.get("first_expert_held", 0)
    held = base["experts_up"].shape[0]
    names = ("up_a", "up_b", "down_a", "down_b")
    pairs = tuple(adapters[f"lora_experts_{n}"] for n in names) \
        if "lora_experts_up_a" in adapters else None

    def expert(acc, stacked):
        e, w_up, w_down, pair = stacked
        w_e = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        up = einsum("td,fd->tf", u, w_up)   # a hidden unit a row
        if pair is not None:
            up = up + _scale(cfg) * dot(dot(u, pair[0]), pair[1])
        hidden = relu2(up)
        out = dot(hidden, w_down)
        if pair is not None:
            out = out + _scale(cfg) * dot(dot(hidden, pair[2]), pair[3])
        return acc + w_e[:, None] * out, None

    if cfg.get("token_block"):
        expert = jax.checkpoint(expert)
    routed = jax.lax.scan(expert, jnp.zeros_like(u), (
        jnp.arange(held), base["experts_up"], base["experts_down"],
        pairs))[0]
    return routed + relu2_mlp(base["shared"], adapters.get("shared", {}), u,
                              cfg)


MIXERS = {"mamba": mamba2, "attn": attention, "moe": sparse_moe}


def block(base, adapters, x, cfg, kind: str):
    """``x + Mixer(RMS(x))``."""
    u = rms(x, base["norm"], cfg["layer_norm_epsilon"])
    return x + MIXERS[kind](base[kind], adapters.get(kind, {}), u, cfg)


def hidden_states(base, adapters, ids, cfg):
    """``ids [T]`` -> the residual stream after the last block ``[T, d]``."""
    x = base["embed"][ids].astype(F32)
    for l, kind in enumerate(kinds(cfg)):
        # A function of its own every time this is traced: ``jax.checkpoint``
        # keeps the trace of a function it has seen, and would hand a second
        # ``PRODUCT_BITS`` the first's products.
        def run(b, a, x, kind=kind):
            return block(b, a, x, cfg, kind)

        if cfg.get("token_block"):
            run = jax.checkpoint(run)
        x = run(base[f"layer_{l}"], adapters.get(f"layer_{l}", {}), x)
    return x


def token_losses(base, x, labels, cfg, pad_id: int = 0):
    """``(sum of the real tokens' cross-entropies, their number)`` from the
    residual stream ``x [T, d]``, a block of tokens at a time where
    ``token_block`` says so."""
    h = rms(x, base["final_norm"], cfg["layer_norm_epsilon"])

    def block_loss(h_rows, y_rows):
        z = dot(h_rows, base["lm_head"])
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, y_rows[:, None], axis=-1)[:, 0]
        return -jnp.sum(picked * (y_rows != pad_id).astype(F32))

    rows = cfg.get("token_block") or h.shape[0]
    total = sum(jax.checkpoint(block_loss)(h[i:i + rows], labels[i:i + rows])
                for i in range(0, h.shape[0], rows))
    return total, jnp.sum((labels != pad_id).astype(F32))


def logits(base, adapters, ids, cfg):
    x = hidden_states(base, adapters, ids, cfg)
    return dot(rms(x, base["final_norm"], cfg["layer_norm_epsilon"]),
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


# --- the base -----------------------------------------------------------------

def init_base(cfg, seed: int):
    """The frozen tree made from ``seed`` by the laws the configuration file
    lists under ``assumed``: every INPUT map and the router normal(0, 0.02);
    ``A_log`` the log of U(1, 16); ``dt_bias`` the inverse softplus of a
    log-uniform step in ``[time_step_min, time_step_max]``; ``D`` and every
    norm 1; the convolution U(-1/2, 1/2) (``conv_kernel`` 4). The embedding
    normal(0, 1) and every block's OUTPUT map (``out_proj``, ``o_proj``, the
    experts' ``down``) normal(0, 0.001), so that a token's own row leads the
    residual stream and a block adds some 0.05-0.08 of its scale, as in
    ``reference_k_exaone.py`` (whose blocks end in a norm of 0.05; this block
    has no norm after its mixer, so the output map carries the scale). Two
    measured reasons (PERF.md section 6, PR 39): what a block computes in
    bf16 then reaches the routers a twentieth as loud, so the 6 chosen
    experts are the float32 reference's but for a few tokens and the
    comparison reads the products' rounding and not flipped choices; and the
    vector common to every token that the adapters learn first has to grow
    to the embedding's size before it moves a router. A fault in one
    block's output still reaches every kind after it: with the first block's
    ``out_proj`` pair left out, the kinds downstream read 4 to 16 times
    their limits (``fed_adapter_ssm_moe_lm_round.TOLERANCES``). The head
    normal(0, 0.002), so that the first logits are small and the first loss is the
    prior's; the router's selection bias normal(0, 0.05) until
    :func:`balance_router` sets it. Drawn in float32 and then narrowed to
    ``cfg["base_dtype"]`` (bfloat16), a tensor at a time on the default
    device. It is what the benchmark hands BOTH the program (``base_params``)
    and this file, so that neither side's weights are the other's. ``seed``
    is any whole number."""
    dtype = jnp.dtype(cfg.get("base_dtype", "bfloat16"))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, n, g, taps = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                        cfg["ssm_state_size"], cfg["n_groups"],
                        cfg["conv_kernel"])
    inner, conv_dim = h * p, h * p + 2 * g * n
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    held, experts = cfg["num_experts_held"], cfg["n_routed_experts"]
    bound = taps ** -0.5
    step = (np.log(cfg.get("time_step_min", 1e-3)),
            np.log(cfg.get("time_step_max", 1e-1)))

    def inverse_softplus(y):
        return y + jnp.log(-jnp.expm1(-y))

    laws = {
        "ones": lambda k, s: jnp.ones(s, F32),
        "conv": lambda k, s: jax.random.uniform(k, s, F32, -bound, bound),
        "A_log": lambda k, s: jnp.log(jax.random.uniform(k, s, F32, 1., 16.)),
        "dt_bias": lambda k, s: inverse_softplus(jnp.exp(jax.random.uniform(
            k, s, F32, *step))),
    }

    def law(key):
        if isinstance(key, float):
            return lambda k, s: key * jax.random.normal(k, s, F32)
        return laws[key]

    draw = jax.jit(lambda name, key, shape: law(name)(key, shape).astype(
        dtype), static_argnums=(0, 2))
    root = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 0xBA5E)

    def drawn(key, tensors):
        return {name: draw(how, jax.random.fold_in(key, i), shape)
                for i, (name, (how, shape)) in enumerate(
                    sorted(tensors.items()))}

    mixers = {
        "mamba": {"in_proj": (0.02, (d, inner + conv_dim + h)),
                  "conv_weight": ("conv", (taps, conv_dim)),
                  "conv_bias": ("conv", (conv_dim,)),
                  "A_log": ("A_log", (h,)), "dt_bias": ("dt_bias", (h,)),
                  "D": ("ones", (h,)), "norm_weight": ("ones", (inner,)),
                  "out_proj": (OUT_STD, (inner, d))},
        "attn": {"q_proj": (0.02, (d, hq * hd)), "k_proj": (0.02, (d, hkv * hd)),
                 "v_proj": (0.02, (d, hkv * hd)),
                 "o_proj": (OUT_STD, (hq * hd, d))},
        "moe": {"router": (0.02, (d, experts)),
                "router_bias": (ROUTER_BIAS_STD, (experts,)),
                "experts_up": (0.02, (held, f, d)),
                "experts_down": (OUT_STD, (held, f, d))},
    }
    shared = {"up_proj": (0.02, (d, fs)), "down_proj": (OUT_STD, (fs, d))}
    tree = {}
    for l, kind in enumerate(kinds(cfg)):
        key = jax.random.fold_in(root, l)
        one = drawn(key, {"norm": ("ones", (d,))})
        one[kind] = drawn(jax.random.fold_in(key, 0), mixers[kind])
        if kind == "moe":
            one[kind]["shared"] = drawn(jax.random.fold_in(key, 1), shared)
        tree[f"layer_{l}"] = one
    last = jax.random.fold_in(root, cfg["num_hidden_layers"])
    tree.update(drawn(last, {"embed": (EMBED_STD, (v, d)),
                             "final_norm": ("ones", (d,)),
                             "lm_head": (HEAD_STD, (d, v))}))
    return tree


def balance_router(base, cfg, ids, steps: int = BALANCE_STEPS,
                   rate: float = BALANCE_RATE):
    """``base`` with every expert block's ``router_bias`` set by the
    auxiliary-loss-free balancing rule of the router this configuration
    carries (DeepSeek-V3's, arXiv:2408.15664): from 0, ``b_e += u sign(mean
    load - load_e)`` over the tokens ``ids [S, T]``, block after block (a
    block's input depends on the biases before it), the load counted over
    ALL ``n_routed_experts``. Returns ``(base', [fullest expert's load over
    the mean an expert block, before and after])``. A router drawn at random
    is not balanced as a trained one is, and whether a seed's favourite
    experts are among the held ones would decide how long its rounds take;
    the bias SELECTS only, it is a frozen tensor of the base like any other
    and both sides read the same. Plain float32 products at the backend's
    default precision: what is wanted is a balance, not a comparison."""
    ids = jnp.asarray(ids)
    sizes = {k: v for k, v in cfg.items() if k != "base"}
    k, experts = sizes["num_experts_per_tok"], sizes["n_routed_experts"]
    eps = sizes["layer_norm_epsilon"]

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
    for l, kind in enumerate(kinds(sizes)):
        one = base[f"layer_{l}"]
        if kind == "moe":
            scores = jax.jit(lambda b, x: jax.nn.sigmoid(dot(rms(
                x, b["norm"], eps), b["moe"]["router"])))
            bias, before, after = settle(jnp.concatenate(
                [scores(one, x) for x in hidden]))
            found.append((float(before), float(after)))
            one = dict(one, moe=dict(one["moe"], router_bias=bias.astype(
                one["moe"]["router_bias"].dtype)))
            base[f"layer_{l}"] = one
        feed = jax.jit(lambda b, x, kind=kind: block(b, {}, x, sizes, kind))
        hidden = [feed(one, x) for x in hidden]
    return base, found


# --- the round ------------------------------------------------------------------

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
