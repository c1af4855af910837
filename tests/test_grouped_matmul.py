"""``ops/grouped_matmul.py``: the grouped kernel (interpreted on the CPU)
against the plain grouped expression and against a loop over the groups,
forward and ``dlhs``, with group edges off the tile grid, empty groups, a total
under the rows (the tail is zero) and transposed matrices; a depth and a width
of 14.5 lane tiles (a partial last tile, masked in depth and clipped in
width); no cotangent for
the frozen matrices; under ``vmap`` with the matrices unbatched (ONE kernel
call with the clients on its grid, a batch of one squeezed) and the loop it
falls back to (batched matrices, a nested batch); what the tally notes; the
plain expression wherever the shapes do not take the kernel; and the kernel
compiled for the v5e at the benchmark cells' shapes (no chip), beside the
attention kernels of two cells that need the same described chip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import grouped_matmul as gm
from fedml_tpu.ops.grouped_matmul import grouped_matmul, takes_kernel

M, K, N, G = 512, 256, 384, 5
# tiles of 128 rows: a 512-row buffer is 4 row tiles, 8 visits at most
SIZES = {
    "edges_off_the_grid": [100, 0, 157, 30, 60],
    "all_empty": [0, 0, 0, 0, 0],
    "one_group_fills_it": [512, 0, 0, 0, 0],
    "a_row_each": [1, 1, 1, 1, 1],
    "edges_on_the_grid": [128, 128, 128, 64, 64],
    "first_groups_empty": [0, 0, 300, 0, 212],
    "five_in_one_tile": [20, 30, 10, 40, 20],
}
TILES = [(128, 128, 128, 128), (256, 256, 128, 128), (512, 128, 384, 128),
         (512, 256, 128, 256)]


def _tiled(lhs, rhs, sizes, tiles, transpose=False):
    """The product at the tests' own tiles (the public call takes the
    module's, which hold a whole test buffer in one row tile)."""
    return gm._grouped_matmul(lhs, rhs, sizes, transpose, tiles,
                              "grouped_matmul")


def _operands(dtype, transpose=False, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.normal(size=(M, K)), dtype)
    rhs = jnp.asarray(rng.normal(size=(G, N, K) if transpose else (G, K, N))
                      * 0.1, dtype)
    return lhs, rhs


def _loop(lhs, rhs, sizes, transpose=False):
    """Group after group, each its rows by its own matrix."""
    out, at = np.zeros((lhs.shape[0], N), np.float32), 0
    for g, size in enumerate(sizes):
        w = np.asarray(rhs[g], np.float32)
        out[at:at + size] = np.asarray(lhs[at:at + size], np.float32) @ (
            w.T if transpose else w)
        at += size
    return out


def _close(got, want, tol):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("tiles", TILES, ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(SIZES))
@pytest.mark.parametrize("transpose", [False, True])
def test_kernel_is_the_plain_grouped_expression(case, tiles, transpose):
    """Forward and ``dlhs`` in float32, every tiling: the kernel, the plain
    masked ``dot_general`` and the loop over the groups agree; rows past the
    groups' total are exactly zero, forward and backward."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs = _operands(jnp.float32, transpose)
    assert takes_kernel(M, K, N)
    cot = jnp.asarray(np.random.default_rng(1).normal(size=(M, N)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda l: _tiled(l, rhs, sizes, tiles, transpose),
                           lhs)
        want, plain_vjp = jax.vjp(
            lambda l: gm.plain(l, rhs, sizes, transpose), lhs)
        (dlhs,), (dlhs_plain,) = vjp(cot), plain_vjp(cot)
    _close(got, want, 1e-5)
    _close(got, _loop(lhs, rhs, SIZES[case], transpose), 1e-5)
    _close(dlhs, dlhs_plain, 1e-5)
    total = sum(SIZES[case])
    assert not np.asarray(got[total:]).any()
    assert not np.asarray(dlhs[total:]).any()


# 14.5 lane tiles, as Nemotron-H's expert width 1,856 is, at a tenth the size:
# 1.45 tiles deep and 2.9 wide, and the other way round for the second matrix
RAGGED = {"deep": (512, 186, 371), "wide": (512, 371, 186)}
RAGGED_SIZES = {
    "groups_under_a_sub_tile_and_empty_ones": [3, 0, 100, 0, 27, 5, 0, 64],
    "edges_off_the_grid": [100, 0, 157, 30, 60, 0, 0, 99],
    "all_empty": [0] * 8,
}


@pytest.mark.parametrize("tiles", [(512, 256, 2048, 128), (256, 128, 128, 128)],
                         ids=lambda t: "x".join(map(str, t)))
@pytest.mark.parametrize("case", list(RAGGED_SIZES))
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("shape", list(RAGGED))
def test_a_partial_last_lane_tile_is_masked_in_depth_and_clipped_in_width(
        shape, transpose, case, tiles):
    """A depth and a width off the lane grid take the kernel: the last depth
    tile's columns past the depth are undefined in both operands (NaN in the
    interpreter) and masked to zero before the product; the last width
    tile's columns past the width are never written. Forward and ``dlhs``
    (the transposed product, ragged the other way) against the plain
    expression, at the module's own tiles and at small ones."""
    m, k, n = RAGGED[shape]
    assert takes_kernel(m, k, n) and k % 128 and n % 128
    sizes = jnp.asarray(RAGGED_SIZES[case], jnp.int32)
    rng = np.random.default_rng(5)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(8, n, k) if transpose else (8, k, n))
                      * 0.1, jnp.float32)
    cot = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda l: _tiled(l, rhs, sizes, tiles, transpose),
                           lhs)
        want, plain_vjp = jax.vjp(
            lambda l: gm.plain(l, rhs, sizes, transpose), lhs)
        (dlhs,), (dlhs_plain,) = vjp(cot), plain_vjp(cot)
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(dlhs)).all()
    _close(got, want, 1e-5)
    _close(dlhs, dlhs_plain, 1e-5)
    total = sum(RAGGED_SIZES[case])
    assert not np.asarray(got[total:]).any()
    assert not np.asarray(dlhs[total:]).any()


@pytest.mark.parametrize("case", ["edges_off_the_grid", "first_groups_empty"])
def test_bfloat16_operands_accumulate_in_float32(case):
    """bf16 rows and matrices: a float32 result one rounding of the operands
    away from the float32 product, and a ``dlhs`` in the rows' dtype."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs = _operands(jnp.bfloat16)
    got, vjp = jax.vjp(lambda l: _tiled(l, rhs, sizes, TILES[1]), lhs)
    assert got.dtype == jnp.float32
    _close(got, _loop(lhs, rhs, SIZES[case]), 1e-5)
    (dlhs,) = vjp(jnp.ones_like(got))
    assert dlhs.dtype == jnp.bfloat16
    want = jax.grad(lambda l: jnp.sum(gm.plain(l, rhs, sizes)))(lhs)
    _close(dlhs, want, 1e-2)


def _dot_shapes(jaxpr):
    """``(operand shapes, result shape)`` of every ``dot_general``, nested
    jaxprs (and kernels' bodies) too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(([tuple(v.aval.shape) for v in eqn.invars],
                          tuple(eqn.outvars[0].aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_dot_shapes(sub))
    return found


@pytest.mark.parametrize("shape", [(M, K, N), (48, 16, 24)],
                         ids=["kernel", "plain"])
def test_the_frozen_matrices_get_no_cotangent(shape):
    """The backward holds no product whose result has the matrices' shape
    (none forms their gradient), kernel and plain expression alike, and
    differentiating by them gives zeros."""
    m, k, n = shape
    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(G, k, n)), jnp.float32)
    sizes = jnp.asarray([m // 8, 0, m // 4, m // 8, m // 16], jnp.int32)
    assert takes_kernel(m, k, n) == (shape == (M, K, N))

    def loss(lhs, rhs):
        return jnp.sum(grouped_matmul(lhs, rhs, sizes) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(lhs, rhs)
    dots = _dot_shapes(jaxpr.jaxpr)
    assert dots
    for operands, result in dots:
        assert result != rhs.shape and result != (G, n, k), (operands, result)
    dlhs, drhs = jax.grad(loss, (0, 1))(lhs, rhs)
    assert np.asarray(dlhs).any() and not np.asarray(drhs).any()


# a client whose total is under the rows with tiles that two groups share,
# one whose groups are all empty, one with groups under a sub-tile
CLIENT_SIZES = [[100, 0, 157, 30, 60, 0, 0, 99], [0] * 8,
                [3, 0, 100, 0, 27, 5, 0, 64]]
# whole lane tiles, and a depth and a width off the lane grid (a partial last
# depth tile, masked)
CLIENT_SHAPES = {"whole_tiles": (M, K, N), "partial_depth": RAGGED["deep"]}


def _clients_operands(clients, shape, transpose, seed=3):
    m, k, n = CLIENT_SHAPES[shape]
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.normal(size=(clients, m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(8, n, k) if transpose else (8, k, n))
                      * 0.1, jnp.float32)
    cot = jnp.asarray(rng.normal(size=(clients, m, n)), jnp.float32)
    return lhs, rhs, jnp.asarray(CLIENT_SIZES[:clients], jnp.int32), cot


def _value_and_dlhs(rhs, transpose):
    """A client's product and its ``dlhs`` for the cotangent ``cot``."""
    def one(lhs, sizes, cot):
        out, vjp = jax.vjp(lambda l: _tiled(l, rhs, sizes, TILES[0],
                                            transpose), lhs)
        return out, vjp(cot)[0]

    return one


def _kernel_calls(jaxpr, within=()):
    """``(enclosing primitives, pallas_call eqn)`` of every kernel call, and
    every ``dynamic_update_slice`` (as ``(within, None)``), nested jaxprs
    too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((within, eqn))
            continue
        if eqn.primitive.name == "dynamic_update_slice":
            found.append((within, None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_kernel_calls(sub, within + (eqn.primitive.name,)))
    return found


def _signature(eqn):
    """What a kernel call lowers from: its body, grid, index maps, block
    shapes and operands."""
    grid = eqn.params["grid_mapping"]
    return (str(eqn.params["jaxpr"]), grid.grid,
            [str(b.index_map_jaxpr) for b in grid.block_mappings],
            [b.block_shape for b in grid.block_mappings],
            [v.aval for v in eqn.invars], [v.aval for v in eqn.outvars])


@pytest.mark.parametrize("shape", list(CLIENT_SHAPES))
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("clients", [1, 2, 3])
def test_under_vmap_the_frozen_matrices_have_no_client_axis(
        clients, transpose, shape):
    """Rows and group sizes batched over clients, the matrices not: each
    client's product and ``dlhs`` are its own call's, bit for bit (an empty
    client, a total under the rows, tiles two groups share, a partial depth
    tile); two or more clients are ONE kernel call a product with the
    clients on its grid, in no loop and with no ``dynamic_update_slice``; a
    batch of one is the unbatched call, squeezed; no value of the batched
    program has a client axis before the matrices' shape."""
    lhs, rhs, sizes, cot = _clients_operands(clients, shape, transpose)
    one = _value_and_dlhs(rhs, transpose)
    values, grads = jax.vmap(one)(lhs, sizes, cot)
    for c in range(clients):
        value, grad = one(lhs[c], sizes[c], cot[c])
        np.testing.assert_array_equal(values[c], value)
        np.testing.assert_array_equal(grads[c], grad)
        assert not np.asarray(values[c][sum(CLIENT_SIZES[c]):]).any()
    jaxpr = jax.make_jaxpr(jax.vmap(one))(lhs, sizes, cot)
    calls = _kernel_calls(jaxpr.jaxpr)
    assert len(calls) == 2                    # the product and its dlhs
    for within, eqn in calls:
        assert eqn is not None and not {"while", "scan"} & set(within)
    if clients == 1:
        alone = jax.make_jaxpr(one)(lhs[0], sizes[0], cot[0])
        assert [_signature(e) for _, e in calls] == [
            _signature(e) for _, e in _kernel_calls(alone.jaxpr)]
    else:
        m, k, _ = CLIENT_SHAPES[shape]
        assert calls[0][1].invars[-2].aval.shape == (clients * m, k)
    text = str(jaxpr)
    assert "f32[{},8,{},{}]".format(clients, *rhs.shape[1:]) not in text


@pytest.mark.parametrize("batch", ["matrices_batched", "nested"])
def test_a_batch_the_grid_does_not_take_falls_back_to_the_loop(batch):
    """Matrices batched with the rows (each client its own), or a ``vmap``
    inside a ``vmap``: the kernel falls back to ``pallas_call``'s loop over
    the (outer) batch, and each client's product and ``dlhs`` are still its
    own call's."""
    lhs, rhs, sizes, cot = _clients_operands(2, "whole_tiles", False)
    if batch == "matrices_batched":
        rhs = jnp.stack([rhs, rhs[::-1]])
        batched = jax.vmap(lambda r, *a: _value_and_dlhs(r, False)(*a))
        values, grads = batched(rhs, lhs, sizes, cot)
        want = [_value_and_dlhs(rhs[c], False)(lhs[c], sizes[c], cot[c])
                for c in range(2)]
        jaxpr = jax.make_jaxpr(batched)(rhs, lhs, sizes, cot)
    else:
        one = _value_and_dlhs(rhs, False)
        pair = lambda a: jnp.stack([a, a[::-1]])  # noqa: E731
        lhs, sizes, cot = pair(lhs), pair(sizes), pair(cot)
        values, grads = jax.vmap(jax.vmap(one))(lhs, sizes, cot)
        want = [[one(lhs[o, c], sizes[o, c], cot[o, c]) for c in range(2)]
                for o in range(2)]
        values, grads = values.reshape(4, *values.shape[2:]), grads.reshape(
            4, *grads.shape[2:])
        want = want[0] + want[1]
        jaxpr = jax.make_jaxpr(jax.vmap(jax.vmap(one)))(lhs, sizes, cot)
    for c, (value, grad) in enumerate(want):
        np.testing.assert_array_equal(values[c], value)
        np.testing.assert_array_equal(grads[c], grad)
    assert any(eqn is None for _, eqn in _kernel_calls(jaxpr.jaxpr))


def test_the_tally_notes_the_clients_on_the_grid():
    """Under ``ops.lora_linear.tally`` a product that takes the kernel notes
    ``Traced(m, k, n, 1)`` when its call is traced, and again with the
    clients when a ``vmap`` of two or more puts them on its grid (its
    ``dlhs`` likewise, ``k`` and ``n`` swapped); a ``vmap`` of one client
    notes no grid, nor does the plain expression anything."""
    from fedml_tpu.ops import lora_linear as ll

    lhs, rhs, sizes, cot = _clients_operands(2, "whole_tiles", False)
    one = _value_and_dlhs(rhs, False)
    for clients in (2, 1):
        with ll.tally() as calls:
            jax.make_jaxpr(jax.vmap(one))(lhs[:clients], sizes[:clients],
                                          cot[:clients])
        grid = {(M, K, N, clients), (M, N, K, clients)} - {
            (M, K, N, 1), (M, N, K, 1)}
        assert set(calls) == {gm.Traced(M, K, N, 1), gm.Traced(M, N, K, 1),
                              *(gm.Traced(*g) for g in grid)}
    with ll.tally() as calls:
        jax.make_jaxpr(jax.vmap(lambda l, s: grouped_matmul(
            l, rhs[:, :16, :24], s)))(lhs[:, :48, :16], sizes)
    assert calls == []


@pytest.mark.parametrize("m,k,n,takes", [
    (8192, 6144, 4096, True),       # the cell's gate and up, a chunk
    (8192, 2048, 6144, True),       # its down projection
    (4096, 1024, 1024, True),       # chip_smoke's
    (128, 128, 128, True),
    (24576, 2688, 1856, True),      # Nemotron-H's experts: 14.5 lane tiles
    (24576, 1856, 2688, True),
    (128, 192, 320, True),          # a partial last tile in depth and width
    (96, 128, 128, False),          # rows under a sub-tile
    (128, 32, 128, False),          # a toy depth
    (128, 128, 24, False),          # a toy width
])
def test_takes_kernel_is_a_function_of_the_shapes(m, k, n, takes):
    assert takes_kernel(m, k, n) is takes


def test_small_shapes_keep_the_plain_expression():
    """Toy widths trace no kernel, and the result is the loop's."""
    rng = np.random.default_rng(4)
    lhs = jnp.asarray(rng.normal(size=(48, 16)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(G, 16, 24)), jnp.float32)
    sizes = [10, 0, 17, 3, 6]
    fn = lambda l: grouped_matmul(l, rhs, jnp.asarray(sizes, jnp.int32))  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(lhs))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(lhs))
    want, at = np.zeros((48, 24), np.float32), 0
    for g, size in enumerate(sizes):
        want[at:at + size] = np.asarray(lhs[at:at + size]) @ np.asarray(rhs[g])
        at += size
    np.testing.assert_allclose(got, want, atol=1e-5)


# --- compiled for the chip, without the chip ---------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e device (the TPU's compiler is installed; nothing
    runs). Made inside the fixture: only the worker that is given this file
    loads the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("groups,m,k,n,transpose,clients", [
    (16, 8192, 6144, 4096, False, 0), (16, 8192, 2048, 6144, False, 0),
    (16, 8192, 4096, 6144, True, 0), (16, 8192, 6144, 2048, True, 0),
    (64, 24576, 2688, 1856, True, 0), (64, 24576, 1856, 2688, False, 0),
    (64, 24576, 2688, 1856, True, 2), (64, 24576, 1856, 2688, False, 2)],
    ids=["gate_up", "down", "gate_up_t", "down_t", "relu2_up_and_down_t",
         "relu2_down_and_up_t", "relu2_up_and_down_t_two_clients",
         "relu2_down_and_up_t_two_clients"])
def test_the_kernel_compiles_for_the_v5e_at_the_cells_shapes(
        monkeypatch, one_chip, groups, m, k, n, transpose, clients):
    """K-EXAONE's held experts, a chunk of 8,192 rows, forward and ``dlhs``,
    and Nemotron-H's 64 of width 1,856 (14.5 lane tiles: ``[64, 1856, 2688]``
    read as it lies, transposed for the up product and ``dlhs`` of the down
    one, plain for the other two), a chunk of 24,576 rows, alone and under a
    ``vmap`` of two clients as the cell's round trains them: Mosaic takes the
    kernel at its own tiles (alignment, the partial tile's masks, VMEM), and
    the two clients' result is the kernel's own, no
    ``dynamic-update-slice`` of a ``[2, 24576, .]`` buffer."""
    monkeypatch.setattr(gm, "pallas_interpret", lambda: False)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    rhs = (groups, n, k) if transpose else (groups, k, n)
    product = lambda l, r, g: grouped_matmul(  # noqa: E731
        l, r, g, transpose_rhs=transpose, name="held_gmm")
    batch = (clients,) if clients else ()
    if clients:
        product = jax.vmap(product, in_axes=(0, None, 0))
    compiled = jax.jit(product).trace(
        spec(batch + (m, k), jnp.bfloat16), spec(rhs, jnp.bfloat16),
        spec(batch + (groups,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "held_gmm" in text
    assert not re.search(r"f32\[2,24576,\d+\][^\n]*dynamic-update-slice",
                         text)


def test_the_band_kernels_compile_for_the_v5e_at_the_cells_shapes(
        monkeypatch, one_chip):
    """K-EXAONE's window layers as the round calls them (``ops/
    flash_attention.py``'s grouped band, PR 37; here because this file holds
    the described chip): a client's sequence of 4,096 tokens under the
    ``vmap``, 64 query heads over 8 key-value heads of 128, a window of 128.
    Mosaic takes the forward, ``dq`` and ``dkv`` kernels (blocks of a whole
    group, the halo's index maps, VMEM) under their stable names."""
    import importlib

    fa = importlib.import_module("fedml_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "pallas_interpret", lambda: False)
    spec = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 1, 4096, heads, 128), jnp.bfloat16, sharding=one_chip)
    step = jax.vmap(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, window=128).astype(jnp.float32)), (0, 1, 2)))
    text = jax.jit(step).trace(spec(64), spec(8), spec(8)).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    for name in ("window_band_fwd", "window_band_dq", "window_band_dkv"):
        assert name in text
    assert "tpu_custom_call" in text


def test_the_selected_block_kernels_compile_for_the_v5e_at_the_cells_shapes(
        monkeypatch, one_chip):
    """MiniMax-M3's attention as ``minimaxm3_lora_c2_s16k``'s round calls it
    (``ops/block_sparse_attention.py``; here because this file holds the
    described chip): a client's 16,384 tokens under the ``vmap``, 64 query
    heads over 4 key-value heads of 128, 18 chosen blocks of 128 a token.
    Mosaic takes the forward, ``dq`` and ``dkv`` kernels (the group's keys
    in VMEM within the default limit, the SMEM token lists, the fetches of
    the ``dkv`` kernel) under their stable names."""
    import importlib

    bsa = importlib.import_module("fedml_tpu.ops.block_sparse_attention")
    monkeypatch.setattr(bsa, "pallas_interpret", lambda: False)
    spec = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 1, 16384) + shape, jnp.int32 if shape == (4, 18)
        else jnp.bfloat16, sharding=one_chip)
    step = jax.vmap(jax.grad(lambda q, k, v, c: jnp.sum(
        bsa.block_sparse_attention(q, k, v, c, 128).astype(jnp.float32)),
        (0, 1, 2)))
    text = jax.jit(step).trace(
        spec(64, 128), spec(4, 128), spec(4, 128), spec(4, 18)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    for name in ("msa_fwd", "msa_dq", "msa_dkv"):
        assert name in text
    assert "tpu_custom_call" in text
