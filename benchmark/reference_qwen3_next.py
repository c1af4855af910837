"""Plain reference of the Qwen3-Next shard this repo trains: the layer
equations in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no chunks, no
sorting, no remat. It imports nothing from the model
(``models/qwen3_next.py``); it reads the same parameter names, so a tree
initialised by the model is an argument here.

Source of the sizes: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/
blob/main/config.json; ``cfg`` is a dict of its keys, with ``num_experts_held``
and ``first_expert_held`` for the shard. Equations (no biases anywhere):

- ``Norm(x) = x rsqrt(mean x^2 + eps) (1 + w)``.
- Layer ``l``: ``h = x + Mixer_l(Norm(x))``, ``y = h + MoE(Norm(h))``; the
  mixer is gated attention when ``(l + 1) % full_attention_interval == 0``,
  else gated DeltaNet.
- Gated DeltaNet: ``[q|k|v|z] = x W_qkvz``, ``[b|a] = x W_ba``; ``q|k|v``
  through a causal depthwise convolution and SiLU; ``q, k`` L2-normalised
  per head (``q`` also over ``sqrt(d_k)``), each key head serving
  ``H_v / H_k`` value heads; ``beta = sigmoid(b)``,
  ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``; per value head, TOKEN
  BY TOKEN: ``S' = alpha_t S``, ``u = beta_t (v_t - S'^T k_t)``,
  ``S = S' + k_t u^T``, ``o_t = S^T q_t``; output
  ``W_o (o rsqrt(mean o^2 + eps) w_n * SiLU(z))``.
- Gated attention: ``[q|g]`` per head from ``W_q``, ``k``, ``v``; ``q, k``
  through ``Norm`` over the head; rotary positions (half-rotation) on the
  first ``partial_rotary_factor`` of the head; DENSE causal softmax with
  scale ``1/sqrt(head_dim)``, ``H_q / H_kv`` query heads a key-value head;
  ``W_o (attn * sigmoid(g))``.
- Experts: ``p = softmax(x W_r)`` over ALL experts; the top
  ``num_experts_per_tok``, weights renormalised over them; EVERY held expert
  is run on EVERY token and masked by the choice; plus
  ``sigmoid(x w_s) E_shared(x)``. What absent experts would add is absent.
- ``logits = Norm(x) W_head``; mean cross-entropy over the tokens whose
  label is not ``pad_id``, a sequence at a time.

Departures from the source, as in the model: no multi-token-prediction
module, no auxiliary router loss; the order of channels inside the fused
matrices is this repo's.

``fedavg_round`` is one FedAvg round: clients in turn, ``epochs`` passes of
plain SGD over their batches, the sample-weighted mean of their models.

So that the published widths at 4,096 tokens fit one chip beside the program
they are compared with, ``cfg["token_block"]`` (unset in the CPU tests'
sizes) computes the same sums a block of tokens at a time: the delta rule's
tokens in blocks whose states alone are kept for the backward pass, the
attention's queries in blocks, and a layer's activations computed again in
the backward pass (``jax.checkpoint``). No equation changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


#: ``None`` computes every product as written (float32). The benchmark's
#: lower-precision reading sets a number of significand bits: both operands
#: of every matrix product are then rounded to it first (the gradient passes
#: straight through the rounding), which is how a chip with narrower
#: multipliers would compute, and what the comparison's limits must catch
#: (8 is bfloat16's, 4 float8 e4m3's). Read while a function is TRACED: set
#: it before ``loss_and_grad`` builds the function that is to use it.
PRODUCT_BITS = None


def _operand(x):
    if PRODUCT_BITS is None:
        return x
    drop = 24 - PRODUCT_BITS        # float32 keeps 24 significand bits
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        ~((1 << drop) - 1) & 0xFFFFFFFF)
    rounded = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return x + jax.lax.stop_gradient(rounded - x)


def dot(a, b):
    return _operand(a) @ _operand(b)


def einsum(spec, a, b):
    return jnp.einsum(spec, _operand(a), _operand(b))


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def delta_rule(q, k, v, alpha, beta, block=None):
    """``q, k [T, H, dk]``, ``v [T, H, dv]``, ``alpha, beta [T, H]``."""

    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = a_t[:, None, None] * state
        u = b_t[:, None] * (v_t - einsum("hkd,hk->hd", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, einsum("hkd,hk->hd", state, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    tokens = (q, k, v, alpha, beta)
    if not block:
        return jax.lax.scan(token, zero, tokens)[1]
    blocks = tuple(a.reshape((-1, block) + a.shape[1:]) for a in tokens)
    out = jax.lax.scan(
        jax.checkpoint(lambda state, b: jax.lax.scan(token, state, b)),
        zero, blocks)[1]
    return out.reshape((-1,) + out.shape[2:])


def gated_deltanet(p, x, cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    t = x.shape[0]
    mixed = dot(x, p["in_proj_qkvz"])
    ba = dot(x, p["in_proj_ba"])
    conv_in, z = mixed[:, :2 * hk * dk + hv * dv], mixed[:, -hv * dv:]
    conv = jnp.zeros_like(conv_in)
    for j in range(taps):           # tap j looks taps - 1 - j tokens back
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, conv_in.shape[1])), conv_in[:t - back]])
        conv = conv + shifted * p["conv_weight"][j]
    conv = silu(conv)
    q = conv[:, :hk * dk].reshape(t, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    serve = hv // hk
    q = jnp.repeat(q, serve, axis=1)
    k = jnp.repeat(k, serve, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[:, hv:] + p["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta, cfg.get("token_block"))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p["norm_weight"]
    return dot(o.reshape(t, hv * dv) * silu(z), p["out_proj"])


def rotary(x, theta, rot):
    """``x [T, H, D]``: positions 0..T-1 on the first ``rot`` dimensions."""
    t = x.shape[0]
    freq = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = np.arange(t, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[:, None, :]
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def gated_attention(p, x, cfg):
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    t = x.shape[0]
    qg = dot(x, p["q_proj"]).reshape(t, hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = dot(x, p["k_proj"]).reshape(t, hkv, hd)
    v = dot(x, p["v_proj"]).reshape(t, hkv, hd)
    rot = int(hd * cfg["partial_rotary_factor"])
    q = rotary(norm(q, p["q_norm"], cfg["rms_norm_eps"]),
               cfg["rope_theta"], rot)
    k = rotary(norm(k, p["k_norm"], cfg["rms_norm_eps"]),
               cfg["rope_theta"], rot)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)

    def attend(q_rows, first):
        """Queries ``first ..`` against every key, the later ones masked."""
        scores = einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(hd)
        seen = (first + np.arange(q_rows.shape[0]))[:, None] \
            >= np.arange(t)[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    block = cfg.get("token_block") or t
    attn = jnp.concatenate([
        jax.checkpoint(attend, static_argnums=1)(q[i:i + block], i)
        for i in range(0, t, block)])
    return dot((attn * jax.nn.sigmoid(gate)).reshape(t, hq * hd),
               p["o_proj"])


def expert(x, gate_up, down):
    f = down.shape[0]
    hidden = dot(x, gate_up)
    return dot(silu(hidden[:, :f]) * hidden[:, f:], down)


def routing_weights(p, x, cfg):
    """``[T, E]``: the renormalised weight of each chosen expert, 0 for the
    others."""
    probs = jax.nn.softmax(dot(x, p["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def sparse_moe(p, x, cfg, shared: bool = True):
    weights = routing_weights(p, x, cfg)
    first = cfg.get("first_expert_held", 0)
    held = p["experts_down"].shape[0]
    mine = weights[:, first:first + held].T             # [held, T]

    def one(out, e):
        gate_up, down, w_e = e
        return out + w_e[:, None] * expert(x, gate_up, down), None

    # every held expert over every token, one after another
    out = jax.lax.scan(one, jnp.zeros_like(x),
                       (p["experts_gate_up"], p["experts_down"], mine))[0]
    if shared:
        out = out + jax.nn.sigmoid(dot(x, p["shared_gate"])) * expert(
            x, p["shared_gate_up"], p["shared_down"])
    return out


def layer(p, x, cfg, full_attention: bool):
    eps = cfg["rms_norm_eps"]
    mixer = gated_attention if full_attention else gated_deltanet
    h = x + mixer(p["mixer"], norm(x, p["input_norm"], eps), cfg)
    return h + sparse_moe(p["moe"], norm(h, p["post_norm"], eps), cfg)


def hidden_states(params, ids, cfg):
    """``ids [T]`` -> the residual stream after the last layer ``[T, d]``."""
    x = params["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        full = (i + 1) % cfg["full_attention_interval"] == 0

        # A function of its own every time this is traced: ``jax.checkpoint``
        # keeps the trace of a function it has seen, and would hand a second
        # ``PRODUCT_BITS`` the first one's products.
        def run(p, x, full=full):
            return layer(p, x, cfg, full)

        if cfg.get("token_block"):
            run = jax.checkpoint(run)
        x = run(params[f"layer_{i}"], x)
    return x


def logits(params, ids, cfg):
    x = hidden_states(params, ids, cfg)
    return dot(norm(x, params["final_norm"], cfg["rms_norm_eps"]),
               params["lm_head"])


def sequence_loss(params, ids, labels, cfg, pad_id: int = 0):
    """Mean cross-entropy of one sequence over its non-pad labels."""
    z = logits(params, ids, cfg)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    real = (labels != pad_id).astype(jnp.float32)
    return -jnp.sum(picked * real) / jnp.maximum(jnp.sum(real), 1.0)


def batch_loss(params, ids, labels, cfg, pad_id: int = 0):
    """``ids, labels [B, T]``: the mean over the batch's sequences."""
    losses = [sequence_loss(params, ids[b], labels[b], cfg, pad_id)
              for b in range(ids.shape[0])]
    return sum(losses) / len(losses)


def loss_and_grad(cfg, pad_id: int = 0):
    """``(params, ids [B, T], labels [B, T]) -> (loss, gradients)``, jitted
    once for every client and step that uses it."""
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: batch_loss(p, x, y, cfg, pad_id)))


def client_update(params, batches, cfg, lr: float, epochs: int = 1,
                  pad_id: int = 0, grad=None):
    """Plain SGD over ``batches`` (a list of ``(ids [B, T], labels [B, T])``)
    in order, ``epochs`` times. Returns ``(params', mean loss)`` with the
    loss averaged over a pass's batches, then over the passes."""
    grad = grad or loss_and_grad(cfg, pad_id)
    epoch_losses = []
    for _ in range(epochs):
        losses = []
        for ids, labels in batches:
            loss, g = grad(params, ids, labels)
            params = jax.tree.map(lambda w, dw: w - lr * dw, params, g)
            losses.append(float(loss))
        epoch_losses.append(np.mean(losses))
    return params, float(np.mean(epoch_losses))


def fedavg_round(params, clients, cfg, lr: float, epochs: int = 1,
                 pad_id: int = 0):
    """``clients``: a list of ``(batches, n_samples)``. Returns the
    sample-weighted mean of the clients' trained models and of their
    losses."""
    total = float(sum(n for _, n in clients))
    mean, loss = None, 0.0
    with jax.default_matmul_precision("highest"):
        grad = loss_and_grad(cfg, pad_id)
        for batches, n in clients:
            trained, client_loss = client_update(params, batches, cfg, lr,
                                                 epochs, pad_id, grad)
            share = jax.tree.map(lambda w: (n / total) * w, trained)
            mean = share if mean is None else jax.tree.map(jnp.add, mean,
                                                           share)
            loss += (n / total) * client_loss
    return mean, loss
