"""The grouped round (``cfg.client_group_size``): the cohort trained ``k``
clients at a time inside one round program, each group folded into a running
weighted sum (``parallel.shard.fold_client_groups``). ``k`` in {1, 2, C}
give the ``k = C`` parameters within float32 summation order; ``k = C`` (and
0) is the parent's round text; what needs the whole stack refuses ``k < C``.

CPU, the forced 8-device platform of ``conftest.py``.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.algos.robust import FedAvgRobustAPI
from fedml_tpu.algos.fedopt import FedOptAPI
from fedml_tpu.data.batching import FederatedArrays, build_federated_arrays
from fedml_tpu.models.cnn import CNNDropOut
from fedml_tpu.parallel.mesh import client_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLIENTS, COHORT, BATCH, CLASSES = 8, 4, 4, 5
#: unequal clients, some with a padded last batch
SIZES = [5, 9, 12, 7, 3, 8, 11, 6]


def _fed():
    rng = np.random.default_rng(0)
    n = sum(SIZES)
    x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, CLASSES, n).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(SIZES)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(CLIENTS)}
    return build_federated_arrays(x, y, parts, BATCH)


def _api(k, mesh=None, cls=FedAvgAPI, cohort=COHORT, nan_guard=False, **more):
    cfg = FedConfig(client_num_in_total=CLIENTS, client_num_per_round=cohort,
                    comm_round=100, epochs=1, batch_size=BATCH, lr=0.05,
                    seed=3, client_group_size=k, **more)
    return cls(CNNDropOut(num_classes=CLASSES), _fed(), None, cfg, mesh=mesh,
               nan_guard=nan_guard)


def _leaves(api):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(api.net)]


@pytest.mark.parametrize("k", [1, 2, COHORT])
def test_groups_of_k_give_the_whole_cohorts_parameters(k):
    """Two rounds, dropout on: the per-client rng streams are keyed by the
    client's slot, so every grouping trains the same clients alike and only
    the order of the float32 sum differs."""
    whole, grouped = _api(0), _api(k)
    for r in range(2):
        a = whole.train_one_round(r)["train_loss"]
        b = grouped.train_one_round(r)["train_loss"]
        assert b == pytest.approx(a, rel=1e-5)
    for want, got in zip(_leaves(whole), _leaves(grouped)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if k == COHORT:     # today's round itself, not a scan of one group
        for want, got in zip(_leaves(whole), _leaves(grouped)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [1, 2])
def test_groups_on_a_mesh_fold_before_the_psum(k):
    """8 clients a round on 2 shards, 4 a shard, ``k`` at a time."""
    mesh = client_mesh(2)
    whole, grouped = _api(0, mesh, cohort=8), _api(k, mesh, cohort=8)
    a = whole.train_one_round(0)["train_loss"]
    b = grouped.train_one_round(0)["train_loss"]
    assert b == pytest.approx(a, rel=1e-5)
    for want, got in zip(_leaves(whole), _leaves(grouped)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_nan_guard_works_a_group_at_a_time():
    """One client's data is NaN: its group's fold leaves it out, and the
    round equals the whole-cohort guarded round."""
    apis = [_api(0, nan_guard=True), _api(1, nan_guard=True)]
    idx, _ = apis[0].sample_round(0)
    for api in apis:
        fed = api.train_fed
        api.train_fed = FederatedArrays(
            x=fed.x.at[int(idx[1])].set(jnp.nan), y=fed.y, mask=fed.mask,
            counts=fed.counts)
        api.train_one_round(0)
    for want, got in zip(*map(_leaves, apis)):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_fedopt_rides_the_grouped_round():
    """The server update sees the grouped mean like any other."""
    whole = _api(0, cls=FedOptAPI, server_optimizer="adam", server_lr=0.01)
    grouped = _api(2, cls=FedOptAPI, server_optimizer="adam", server_lr=0.01)
    for api in (whole, grouped):
        api.train_one_round(0)
    for want, got in zip(_leaves(whole), _leaves(grouped)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("what, more", [
    ("aggregator", {"aggregator": "coord_median"}),
    ("aggregator", {"aggregator": "krum1"}),
    ("client_transform", {"compress": "topk0.5"}),
])
def test_what_needs_the_whole_stack_refuses_k_below_c(what, more):
    with pytest.raises(NotImplementedError, match=what):
        _api(2, **more)
    _api(COHORT, **more).train_one_round(0)     # k = C is today's round


def test_refusals_of_the_drill_a_custom_round_and_a_k_that_does_not_divide():
    with pytest.raises(NotImplementedError, match="corruptor|client_transform"):
        _api(2, cls=FedAvgRobustAPI, corrupt_mode="sign_flip", attack_freq=1)
    from fedml_tpu.algos.scaffold import ScaffoldAPI

    with pytest.raises(NotImplementedError, match="client_group_size"):
        _api(2, cls=ScaffoldAPI)
    with pytest.raises(ValueError, match="does not divide"):
        _api(3)
    from fedml_tpu.parallel.shard import make_vmap_round

    def local_train(net, x, y, mask, rng):
        return net, jnp.zeros(())

    round_fn = make_vmap_round(local_train, client_transform=lambda g, c: c,
                               group=2)
    ones = jnp.ones((4, 1, 2))
    with pytest.raises(NotImplementedError, match="client_transform"):
        round_fn({"w": jnp.ones(3)}, ones, ones, ones, jnp.ones(4),
                 jnp.ones(4), jax.random.PRNGKey(0))


# --- k = C is the parent's round, byte for byte ---------------------------

def _resident_steps(mix: dict, config: dict, batch: int) -> int:
    """Steps a client of a resident mix: every client is padded to the
    largest, which the mix's own law and seed give."""
    if "lda" not in mix:
        return -(-int(mix["counts"]["per_client"]) // batch)
    from fedml_tpu.data.partition import partition_dirichlet

    lda = mix["lda"]
    y = np.random.RandomState(int(lda["seed"])).permutation(np.repeat(
        np.arange(int(config["classes"]), dtype=np.int32),
        int(lda["samples_per_class"])))
    parts = partition_dirichlet(y, int(mix["clients"]), float(lda["alpha"]),
                                seed=int(lda["seed"]))
    return -(-max(len(p) for p in parts.values()) // batch)


def _cell_step_text(cell: str) -> str:
    """The lowered text of the round step ``train_one_round`` dispatches in
    an accepted cell of the benchmark: its configuration's model and
    ``fed_config``, its mix's cohort, batch and client size, its chips."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [w for w in manifest["workloads"] if w["name"] == cell]
    config_file, = [c["file"] for c in manifest["configs"]
                    if c["name"] == entry["config"]]
    with open(os.path.join(ROOT, config_file)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           entry["traffic"] + ".json")) as f:
        mix = json.load(f)
    import importlib

    module, _, attr = config["factory"].rpartition(".")
    model = getattr(importlib.import_module(module), attr)(
        **config["factory_kwargs"])
    batch, cohort = int(mix["batch"]), int(mix["cohort"])
    shape = tuple(config["input_shape"])
    mesh = client_mesh(4) if entry["chips"] == 4 else None
    resident = mix["placement"] == "resident"
    steps = _resident_steps(mix, config, batch) if resident else 5
    # the API is built on a federation of one step; the step is lowered on
    # the shapes of the cell's own
    small = build_federated_arrays(
        np.zeros((cohort * batch,) + shape, np.float32),
        np.zeros(cohort * batch, np.int32),
        {c: np.arange(c * batch, (c + 1) * batch) for c in range(cohort)},
        batch)
    cfg = FedConfig(client_num_in_total=cohort, client_num_per_round=cohort,
                    comm_round=10, epochs=int(mix["epochs"]),
                    batch_size=batch, lr=float(mix["lr"]), seed=0,
                    **config.get("fed_config", {}))
    api = FedAvgAPI(model, small, None, cfg, mesh=mesh)
    pre, gather = api._fused_round_step()
    spec = jax.ShapeDtypeStruct
    key = jax.random.PRNGKey(0)
    if resident:
        n = int(mix["clients"])
        fed = FederatedArrays(
            x=spec((n, steps, batch) + shape, jnp.float32),
            y=spec((n, steps, batch), jnp.int32),
            mask=spec((n, steps, batch), jnp.float32),
            counts=spec((n,), jnp.int32))
        lowered = gather.lower(
            api.net, api._window_carry_init(), fed,
            spec((cohort,), jnp.int32), spec((cohort,), jnp.float32), key)
    else:
        lowered = pre.lower(
            api.net, api._window_carry_init(),
            spec((cohort, steps, batch) + shape, jnp.float32),
            spec((cohort, steps, batch), jnp.int32),
            spec((cohort, steps, batch), jnp.float32),
            spec((cohort,), jnp.float32), key)
    return lowered.as_text()


def _grouped_toy_step_text() -> str:
    """The grouped round (``client_group_size`` 1) that ``qwen3next_c4_s4k``
    dispatches, with the cell's ``fed_config`` and cohort (4 silos a round,
    2 sequences each, batch 1) at the widths of ``tests/test_qwen3_next.py``:
    the published ones are 424 M parameters."""
    from functools import partial

    from test_qwen3_next import SMALL, T

    from fedml_tpu.models import create_model
    from fedml_tpu.trainer.local import seq_softmax_ce

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        fed_config = json.load(f)["fed_config"]
    assert fed_config["client_group_size"] == 1
    clients, cohort, per_client = 8, 4, 2
    ids = np.ones((clients * per_client, T), np.int32)
    small = build_federated_arrays(
        ids, ids, {c: np.arange(c * per_client, (c + 1) * per_client)
                   for c in range(clients)}, 1)
    cfg = FedConfig(client_num_in_total=clients, client_num_per_round=cohort,
                    comm_round=10, epochs=1, batch_size=1, lr=0.2, seed=0,
                    **fed_config)
    api = FedAvgAPI(create_model("qwen3_next", **SMALL), small, None, cfg,
                    loss_fn=partial(seq_softmax_ce, pad_id=0))
    _, gather = api._fused_round_step()
    spec = jax.ShapeDtypeStruct
    return gather.lower(
        api.net, api._window_carry_init(), small,
        spec((cohort,), jnp.int32), spec((cohort,), jnp.float32),
        jax.random.PRNGKey(0)).as_text()


@pytest.mark.parametrize("cell", ["resnet56_c16", "femnist_cnn_3400",
                                  "resnet56_c64_4chip", "resnet56_lda_c10",
                                  "grouped_round_toy"])
def test_the_accepted_cells_round_step_is_the_parents_text(cell):
    """``tests/fixtures/round_step_text.json`` holds the SHA-256 of this
    text as the parent of the PR that pinned it lowered it (the same
    function run in a clone of that commit): ecc2d4e for the first three,
    c7fc5db for ``resnet56_lda_c10`` (27 steps) and for the grouped round
    at toy widths, which the size-grouped store round shares its fold with.
    ``femnist_cnn_3400`` is the whole-cohort step, the fall-back there."""
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "round_step_text.json")) as f:
        want = json.load(f)["sha256"][cell]
    text = (_grouped_toy_step_text() if cell == "grouped_round_toy"
            else _cell_step_text(cell))
    assert hashlib.sha256(text.encode()).hexdigest() == want
