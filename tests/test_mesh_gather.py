"""The resident cohort gather inside the sharded round: on a client mesh
the federation is replicated and each shard takes its own sampled clients
from its local copy, in the round's one program
(``parallel.shard.make_cohort_gather``). Compared with the path it
replaced, rebuilt here: an eager ``gather_clients`` from the caller's
single-device federation, resharded into the ``pre`` step.

CPU, the forced 8-device platform of ``conftest.py``; ``client_mesh(4)``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data.batching import build_federated_arrays, gather_clients
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.obs.sanitizer import compile_count
from fedml_tpu.parallel.mesh import client_mesh

CLIENTS, FEATURES, CLASSES, BATCH = 12, 6, 3, 8
#: unequal clients: 1 to 3 steps of 8, most with a padded last batch
SIZES = [5, 9, 12, 16, 7, 20, 11, 3, 14, 8, 18, 6]


def _fed(sizes=SIZES):
    rng = np.random.default_rng(0)
    n = sum(sizes)
    x = rng.normal(size=(n, FEATURES)).astype(np.float32)
    y = rng.integers(0, CLASSES, n).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(sizes)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(len(sizes))}
    return build_federated_arrays(x, y, parts, BATCH)


def _api(fed, per_round, mesh):
    cfg = FedConfig(client_num_in_total=CLIENTS,
                    client_num_per_round=per_round, comm_round=100, epochs=1,
                    batch_size=BATCH, lr=0.1, seed=0)
    return FedAvgAPI(LogisticRegression(num_classes=CLASSES), fed, None, cfg,
                     mesh=mesh)


def _parent_round(api, fed, round_idx):
    """One round as the mesh branch ran it before the gather moved into
    the program: the same keys and sample, ``gather_clients`` eagerly from
    the caller's (single-device) ``fed``, then the ``pre`` step."""
    pre, _ = api._fused_round_step()
    api.rng, key = jax.random.split(api.rng)
    idx, wmask = api.sample_round(round_idx)
    sub = gather_clients(fed, idx)
    weights = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
    (api.net, extra), loss = pre(api.net, api._window_carry_init(), sub.x,
                                 sub.y, sub.mask, weights, key)
    api._window_carry_commit(extra)
    return float(loss)


def _assert_params_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a.net.params),
                      jax.tree.leaves(b.net.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_mesh_rounds_bit_equal_to_the_eager_gather_they_replaced():
    """Three fused rounds, 6 of 12 unequal clients padded to 8 slots on 4
    shards: sampled indices cross shard boundaries, two pad slots and one
    empty client carry no weight."""
    sizes = list(SIZES)
    empty = int(sample_clients(1, CLIENTS, 6)[2])
    sizes[empty] = 0
    fed = _fed(sizes)
    mesh = client_mesh(4)
    new, old, one = _api(fed, 6, mesh), _api(fed, 6, mesh), _api(fed, 6, None)
    idx, wmask = new.sample_round(1)
    assert len(idx) == 8 and wmask.tolist() == [1.0] * 6 + [0.0] * 2
    assert empty in idx[:6].tolist()
    # slot i lands on shard i // 2: the cohort is not shard-aligned
    assert any(int(c) // (CLIENTS // 4) != slot // 2
               for slot, c in enumerate(idx))
    for r in range(3):
        got = new.train_one_round(r)["train_loss"]
        assert got == _parent_round(old, fed, r)
        np.testing.assert_allclose(got, one.train_one_round(r)["train_loss"],
                                   atol=2e-5)
    _assert_params_equal(new, old)
    for a, b in zip(jax.tree.leaves(new.net.params),
                    jax.tree.leaves(one.net.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_federation_is_replicated_over_the_mesh_and_the_callers_is_not():
    fed = _fed()
    before = [(leaf.sharding, np.asarray(leaf)) for leaf in jax.tree.leaves(fed)]
    mesh = client_mesh(4)
    api = _api(fed, 6, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    for leaf, mine in zip(jax.tree.leaves(api.train_fed),
                          jax.tree.leaves(fed)):
        assert leaf.sharding.is_equivalent_to(replicated, leaf.ndim)
        assert leaf.sharding.device_set == set(mesh.devices.flat)
        assert leaf.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(mine))
    for leaf, (sharding, value) in zip(jax.tree.leaves(fed), before):
        assert leaf.sharding == sharding and len(leaf.sharding.device_set) == 1
        np.testing.assert_array_equal(np.asarray(leaf), value)
    assert api._fused_round_step()[1] is not None
    assert _api(fed, 6, None).train_fed is fed


def test_no_shard_waits_for_anothers_data():
    """The compiled gather step moves no cohort data between chips: the
    only collective is the aggregation's all-reduce."""
    api = _api(_fed(), 6, client_mesh(4))
    _, gather = api._fused_round_step()
    idx, wmask = api.sample_round(0)
    hlo = gather.lower(api.net, api._window_carry_init(), api.train_fed,
                       jnp.asarray(idx), jnp.asarray(wmask),
                       jax.random.PRNGKey(0)).compile().as_text()
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in hlo, op
    assert "all-reduce" in hlo


@pytest.mark.parametrize("phases", [("host", "scan", "host"),
                                    ("scan", "host", "scan")])
def test_host_loop_and_scan_share_the_replicated_federation(phases):
    """Host loop and whole-run scan alternate on one mesh API (full
    participation, as the scan requires) and equal six rounds of the eager
    path; the scan leaves ``train_fed`` where it was, so the third phase
    compiles nothing."""
    fed = _fed()
    mesh = client_mesh(4)
    api, old = _api(fed, CLIENTS, mesh), _api(fed, CLIENTS, mesh)
    placed = jax.tree.leaves(api.train_fed)
    losses = []

    def run(kind):
        first = len(losses)
        if kind == "host":
            losses.extend(api.train_one_round(r)["train_loss"]
                          for r in (first, first + 1))
        else:
            losses.extend(np.asarray(api.train_rounds_on_device(2)).tolist())

    run(phases[0])
    run(phases[1])
    compiled = compile_count()
    run(phases[2])
    assert compile_count() == compiled
    for leaf, was in zip(jax.tree.leaves(api.train_fed), placed):
        assert leaf is was
    want = [_parent_round(old, fed, r) for r in range(6)]
    np.testing.assert_allclose(losses, want, rtol=1e-6, atol=1e-6)
    _assert_params_equal(api, old)
