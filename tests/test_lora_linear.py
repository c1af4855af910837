"""``ops/lora_linear.py``: the one-pass kernel (interpreted on the CPU)
against the plain expression, values and gradients, with and without the
gate, over more than one ``k`` step and a partial last ``n`` tile; under
``vmap`` with the frozen matrix unbatched; and the plain expression, bit for
bit, wherever the shapes do not take the kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import lora_linear as ll
from fedml_tpu.ops.lora_linear import lora_linear, takes_kernel

SCALE = 2.0
# tiles of 32 x 256 x 128: K = 384 is three steps, N = 704 is 2.75 tiles
M, K, N, N_GATE, RANK = 64, 384, 704, 1024, 8


@pytest.fixture(params=[128, K], ids=["k_in_3_steps", "k_in_1_step"])
def small_tiles(monkeypatch, request):
    """The kernel at the tests' sizes: small tiles, no least result; the
    depth in three accumulated steps, and whole (no accumulator)."""
    monkeypatch.setattr(ll, "MIN_RESULT", 1)
    for name, size in (("TM", 32), ("TN", 256), ("TK", request.param)):
        monkeypatch.setattr(ll, name, size)


def _plain(x, w, a, b, gate, out_dtype):
    """What ``models/granite_hybrid.py`` wrote before the kernel."""
    def mm(p, q):
        return jnp.einsum("...d,de->...e", p, q.astype(p.dtype),
                          preferred_element_type=jnp.float32)

    s = mm(x, w) + SCALE * mm(mm(x, a).astype(x.dtype), b)
    if gate:
        f = s.shape[-1] // 2
        s = jax.nn.silu(s[..., :f]) * s[..., f:]
    return s.astype(out_dtype)


def _operands(dtype, n, lead=(2, M // 2), clients=None):
    keys = jax.random.split(jax.random.PRNGKey(n), 5)
    batch = () if clients is None else (clients,)
    x = jax.random.normal(keys[0], batch + lead + (K,), dtype)
    w = (jax.random.normal(keys[1], (K, n)) * 0.05).astype(dtype)
    a = jax.random.normal(keys[2], batch + (K, RANK)) * 0.05
    b = jax.random.normal(keys[3], batch + (RANK, n)) * 0.05
    return keys[4], x, w, a, b


def _close(got, want, tol):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("gate", [False, True])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_kernel_is_the_plain_expression(small_tiles, dtype, tol, gate):
    n = N_GATE if gate else N
    out_dtype = dtype if gate else jnp.float32
    key, x, w, a, b = _operands(dtype, n)
    assert takes_kernel(M, K, n, RANK, gate)
    got, got_vjp = jax.vjp(lambda x, a, b: lora_linear(
        x, w, a, b, SCALE, gate=gate, out_dtype=out_dtype), x, a, b)
    want, want_vjp = jax.vjp(
        lambda x, a, b: _plain(x, w, a, b, gate, out_dtype), x, a, b)
    assert got.dtype == want.dtype == out_dtype
    _close(got, want, tol)
    # the primal (no residuals) is the same pass
    _close(lora_linear(x, w, a, b, SCALE, gate=gate, out_dtype=out_dtype),
           want, tol)
    cot = jax.random.normal(key, want.shape, out_dtype)
    for g, h in zip(got_vjp(cot), want_vjp(cot)):
        assert g.dtype == h.dtype
        _close(g, h, tol)


def test_the_frozen_matrix_gets_its_cotangent(small_tiles):
    key, x, w, a, b = _operands(jnp.float32, N_GATE)
    grads = [jax.grad(lambda w: jnp.sum(f(w) ** 2))(w) for f in (
        lambda w: lora_linear(x, w, a, b, SCALE, gate=True),
        lambda w: _plain(x, w, a, b, True, jnp.float32))]
    _close(*grads, 2e-5)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("gate", [False, True])
def test_under_vmap_the_frozen_matrix_has_no_client_axis(small_tiles, gate):
    clients, n = 3, N_GATE if gate else N
    key, x, w, a, b = _operands(jnp.bfloat16, n, lead=(1, M), clients=clients)

    def round_(w, x, a, b):
        return jax.vmap(lambda x, a, b: lora_linear(
            x, w, a, b, SCALE, gate=gate, out_dtype=jnp.float32))(x, a, b)

    want = jax.vmap(lambda x, a, b: _plain(x, w, a, b, gate, jnp.float32))(
        x, a, b)
    _close(round_(w, x, a, b), want, 1e-2)
    call, = _pallas_calls(jax.make_jaxpr(round_)(w, x, a, b).jaxpr)
    shapes = [v.aval.shape for v in call.invars]
    halves = 2 if gate else 1
    # x, t = x A, then W and B once a half
    assert shapes[0] == (clients, M, K) and shapes[1] == (clients, M, RANK)
    assert shapes[2:2 + halves] == [(K, n)] * halves        # read, not copied
    assert shapes[2 + halves:] == [(clients, RANK, n)] * halves
    assert call.params["grid_mapping"].grid[0] == clients
    # the round's own gradient: the batched backward is plain JAX
    grads = [jax.grad(lambda a: jnp.sum(f(a) ** 2))(a) for f in (
        lambda a: round_(w, x, a, b),
        lambda a: jax.vmap(lambda x, a, b: _plain(
            x, w, a, b, gate, jnp.float32))(x, a, b))]
    _close(*grads, 2e-2)


@pytest.mark.parametrize("gate", [False, True])
def test_a_rematerialised_layer_saves_the_sum_only_where_it_is_used(
        small_tiles, gate):
    """The first forward of a checkpointed layer runs the kernel that writes
    the output alone; the gated layer's recomputed forward the one that also
    writes the float32 sum for the gate's derivative; the linear one has
    nothing to recompute."""
    n = N_GATE if gate else N
    _, x, w, a, b = _operands(jnp.float32, n)
    layer = jax.checkpoint(lambda a: lora_linear(x, w, a, b, SCALE,
                                                 gate=gate))
    jaxpr = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(layer(a) ** 2)))(a)
    names = sorted(c.params["name"] for c in _pallas_calls(jaxpr.jaxpr))
    assert names == (["lora_linear_gate", "lora_linear_gate_saved"] if gate
                     else ["lora_linear"])


@pytest.mark.parametrize("m,k,n,rank,gate,takes", [
    (1024, 2048, 16384, 16, True, True),     # input_linear, a client
    (1024, 2048, 8512, 16, False, False),    # in_proj: its consumers' slices
    (2048, 2048, 8512, 16, False, True),     # 66.5 x 128 columns, large enough
    (1024, 4096, 2048, 16, False, False),    # out_proj: XLA's fusion is enough
    (1024, 8192, 2048, 16, False, False),    # output_linear
    (1024, 2048, 512, 16, False, False),     # k_proj, v_proj
    (1024, 2000, 16384, 16, False, False),   # a depth of partial lanes
    (1000, 2048, 16384, 16, False, False),   # rows of partial sublanes
    (1024, 2048, 16384, 0, False, False),    # no pair
    (1024, 2048, 16512, 16, True, False),    # gated halves of 64.5 x 128
    (24, 64, 192, 4, True, False),           # the CPU tests' widths
])
def test_takes_kernel_is_a_function_of_the_shapes(m, k, n, rank, gate, takes):
    assert takes_kernel(m, k, n, rank, gate) is takes


@pytest.mark.parametrize("gate", [False, True])
def test_small_shapes_keep_the_plain_expression_bit_for_bit(gate):
    n = N_GATE if gate else N
    key, x, w, a, b = _operands(jnp.bfloat16, n)
    assert not takes_kernel(M, K, n, RANK, gate)
    out_dtype = jnp.bfloat16 if gate else jnp.float32

    def run(f):
        y, vjp = jax.vjp(f, x, a, b)
        return (y,) + vjp(jax.random.normal(key, y.shape, y.dtype))

    got = run(lambda x, a, b: lora_linear(x, w, a, b, SCALE, gate=gate,
                                          out_dtype=out_dtype))
    want = run(lambda x, a, b: _plain(x, w, a, b, gate, out_dtype))
    for g, h in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(h, np.float32))
    jaxpr = jax.make_jaxpr(lambda x: lora_linear(x, w, a, b, SCALE,
                                                 gate=gate))(x)
    assert not _pallas_calls(jaxpr.jaxpr)


def test_the_tally_sees_every_traced_projection():
    _, x, w, a, b = _operands(jnp.float32, N)
    with ll.tally() as calls:
        jax.eval_shape(lambda x: lora_linear(x, w, a, b, SCALE), x)
    assert calls == [(M, K, N, RANK, False, 0)]
    jax.eval_shape(lambda x: lora_linear(x, w, a, b, SCALE), x)
    assert len(calls) == 1      # closed: nothing is added afterwards
