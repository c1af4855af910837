"""Frozen-base adapter finetuning (PR 15): the split/merge seam, the
FedAdapterAPI tiers (windowed/on-device bit-equality, zero
steady-state recompiles, checkpoint at a window boundary incl. the
personalized adapter stacks), the frozen base's fp32 bitwise invariance
(host loop AND under the codec on the message-passing tiers), the
negotiated delta capability (sync accepts adapter frames; a delta sender
refuses a delta-ignorant peer; a mismatched stamp is refused, not
mis-folded), and the driver flag-rejection matrix."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedadapter import FedAdapterAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.models.adapter import (
    adapter_model_fns,
    merge_params,
    param_count,
    split_frozen,
)
from fedml_tpu.models.registry import create_model
from fedml_tpu.trainer.local import NetState, model_fns, seq_softmax_ce

V, T, B = 32, 16, 4
LOSS = partial(seq_softmax_ce, pad_id=0)


def _model(rank=4, scope="attn", d_model=32):
    return create_model("transformer_lm", vocab_size=V, d_model=d_model,
                        n_heads=2, n_layers=2, max_len=T,
                        adapter_rank=rank, adapter_scope=scope)


def _token_data(n_clients=6, per=8, seed=0):
    rng = np.random.RandomState(seed)
    seqs = rng.randint(1, V, size=(n_clients * per, T + 1))
    x = seqs[:, :T].astype(np.int32)
    y = seqs[:, 1:].astype(np.int32)
    return x, y, partition_homo(len(x), n_clients)


def _cfg(n=6, cpr=3, rounds=7, **kw):
    kw.setdefault("lr", 0.1)
    kw.setdefault("epochs", 1)
    kw.setdefault("seed", 0)
    kw.setdefault("frequency_of_the_test", 1000)
    return FedConfig(client_num_in_total=n, client_num_per_round=cpr,
                     comm_round=rounds, batch_size=B, **kw)


def _mk(train, **api_kw):
    return FedAdapterAPI(_model(), train, None, _cfg(), loss_fn=LOSS,
                         **api_kw)


def _trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _snap(tree):
    return jax.tree.map(np.asarray, tree)


# ------------------------------------------------------- the model seam --

def test_split_merge_bijection():
    """split_frozen / merge_params is a lossless bijection on a real
    injected param tree, and the split is exactly the lora_ leaves."""
    fns = model_fns(_model(rank=4, scope="all"))
    full = fns.init(jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))
    base, adapters = split_frozen(full.params)
    assert jax.tree.leaves(adapters), "no adapter leaves split off"

    def names(tree, prefix=""):
        out = []
        for k, v in tree.items():
            if isinstance(v, dict):
                out += names(v, prefix + k + "/")
            else:
                out.append(prefix + k)
        return out

    assert all("lora_" in n.rsplit("/", 1)[-1] for n in names(adapters))
    assert not any("lora_" in n.rsplit("/", 1)[-1] for n in names(base))
    merged = merge_params(base, adapters)
    assert jax.tree.structure(merged) == jax.tree.structure(full.params)
    _trees_equal(merged, full.params)


def test_merge_collision_refused():
    with pytest.raises(ValueError, match="collide"):
        merge_params({"a": np.zeros(2)}, {"a": np.zeros(2)})


def test_rank0_tree_identical_to_dense():
    """adapter_rank=0 leaves the param tree identical to the pre-LoRA
    model — dense checkpoints stay loadable."""
    dense = model_fns(create_model("transformer_lm", vocab_size=V,
                                   d_model=32, n_heads=2, n_layers=2,
                                   max_len=T))
    rank0 = model_fns(_model(rank=0))
    a = dense.init(jax.random.PRNGKey(3), jnp.zeros((1, T), jnp.int32))
    b = rank0.init(jax.random.PRNGKey(3), jnp.zeros((1, T), jnp.int32))
    assert (jax.tree.structure(a.params) == jax.tree.structure(b.params))
    _trees_equal(a.params, b.params)


def test_adapter_init_is_exact_identity():
    """B = 0 at init: the injected model's forward equals the dense
    model's bitwise (the LoRA residual is exactly zero)."""
    x = jnp.asarray(np.random.RandomState(0).randint(1, V, (2, T)))
    dense = model_fns(_model(rank=0))
    lora_fns = adapter_model_fns(_model(rank=4, scope="all"))
    net = lora_fns.init(jax.random.PRNGKey(5), x)
    base = lora_fns.holder["base"]
    da, _ = dense.apply(NetState(base, {}), x)
    la, _ = lora_fns.apply(net, x)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(la))


def test_the_profile_counts_the_grouped_products_with_clients_on_the_grid():
    """``adapter_profile()``'s ``grouped_products`` and
    ``grouped_products_client_grid``: none before a program that holds a
    grouped product is traced (this model has none); then the shapes of the
    products that took ``ops.grouped_matmul``'s kernel in the API's own
    programs, and of those the ones a ``vmap`` of two clients lowered to one
    call with the clients on its grid (not one client's)."""
    from fedml_tpu.ops.grouped_matmul import grouped_matmul

    x, y, parts = _token_data()
    api = _mk(build_federated_arrays(x, y, parts, B))
    api.train_one_round(0)
    profile = api.adapter_profile()
    assert (profile["grouped_products"],
            profile["grouped_products_client_grid"]) == (0, 0)
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.normal(size=(3, 128, 256)), jnp.float32)
    batched = jax.vmap(lambda l, s: grouped_matmul(l, rhs, s))
    for clients, rows in ((2, 256), (1, 128)):
        sizes = jnp.asarray([[100, 0, 28]] * clients, jnp.int32)
        lhs = jnp.ones((clients, rows, 128), jnp.float32)
        out = api._jit(batched)(lhs, sizes)
        assert out.shape == (clients, rows, 256)
    profile = api.adapter_profile()
    assert (profile["grouped_products"],
            profile["grouped_products_client_grid"]) == (2, 1)


def test_pretrained_base_params_swap():
    """base_params swaps a dense checkpoint in as the frozen base; at
    the identity adapter init the merged forward equals the dense
    checkpoint's forward bitwise. A mismatched structure refuses."""
    x = jnp.asarray(np.random.RandomState(1).randint(1, V, (2, T)))
    dense = model_fns(_model(rank=0))
    ckpt = dense.init(jax.random.PRNGKey(7), x)
    fns = adapter_model_fns(_model(rank=4), base_params=ckpt.params)
    net = fns.init(jax.random.PRNGKey(0), x)
    _trees_equal(fns.holder["base"], ckpt.params)
    da, _ = dense.apply(ckpt, x)
    la, _ = fns.apply(net, x)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(la))
    bad = adapter_model_fns(_model(rank=4),
                            base_params={"wrong": np.zeros(3)})
    with pytest.raises(ValueError, match="structure"):
        bad.init(jax.random.PRNGKey(0), x)


def test_dense_model_refused():
    """An adapter config against a dense model must refuse loudly, not
    silently train the dense arm."""
    x, y, parts = _token_data()
    fed = build_federated_arrays(x, y, parts, B)
    with pytest.raises(ValueError, match="adapter_rank > 0"):
        FedAdapterAPI(_model(rank=0), fed, None, _cfg(), loss_fn=LOSS)


def test_bad_scope_and_rank_refused():
    with pytest.raises(ValueError, match="adapter_scope"):
        create_model("transformer_lm", vocab_size=V, adapter_rank=2,
                     adapter_scope="everything")
    with pytest.raises(ValueError, match="adapter_rank"):
        create_model("transformer_lm", vocab_size=V, adapter_rank=-1)


def test_adapter_cfg_refused_on_other_algorithms():
    """cfg.adapter_rank on a non-adapter simulator API is the silent-
    dense-arm drift the convention refuses (PR 4)."""
    from fedml_tpu.algos.fedavg import FedAvgAPI
    from fedml_tpu.algos.fedprox import FedProxAPI

    x, y, parts = _token_data()
    fed = build_federated_arrays(x, y, parts, B)
    for cls in (FedAvgAPI, FedProxAPI):
        with pytest.raises(NotImplementedError, match="adapter"):
            cls(_model(rank=4), fed, None, _cfg(adapter_rank=4),
                loss_fn=LOSS)


# --------------------------------------------------- the simulator tiers --

def test_frozen_base_bitwise_invariant_10_rounds():
    """The acceptance pin: fp32 frozen base bitwise-identical across a
    10-round host-loop run (and the federated net IS the adapter tree)."""
    x, y, parts = _token_data()
    api = FedAdapterAPI(_model(), build_federated_arrays(x, y, parts, B),
                        None, _cfg(rounds=10), loss_fn=LOSS)
    base0 = _snap(api.base)
    adapters0 = _snap(api.net.params)
    for r in range(10):
        api.train_one_round(r)
    _trees_equal(base0, api.base)
    # ... and training actually moved the adapters.
    moved = any(not np.array_equal(a, np.asarray(b))
                for a, b in zip(jax.tree.leaves(adapters0),
                                jax.tree.leaves(api.net.params)))
    assert moved
    prof = api.adapter_profile()
    assert prof["adapter_params"] == param_count(api.net.params)
    assert 0 < prof["adapter_ratio"] < 0.5


@pytest.mark.slow  # >7 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_vs_host_bit_equal_non_dividing():
    """FedAdapter rides the windowed scan bit-equal at a non-dividing W
    (the acceptance pin), streaming from a FederatedStore."""
    x, y, parts = _token_data()
    host = _mk(build_federated_arrays(x, y, parts, B))
    la = [host.train_one_round(r)["train_loss"] for r in range(7)]
    win = _mk(FederatedStore(x, y, parts, batch_size=B))
    base0 = _snap(win.base)
    lb = win.train_rounds_windowed(7, window=3)
    np.testing.assert_array_equal(la, lb)
    _trees_equal(host.net.params, win.net.params)
    _trees_equal(base0, win.base)  # frozen through the scan too


def test_on_device_scan_runs():
    """The on-device scan (derived from the same record) trains the
    adapter tree with the base as a jit-captured constant."""
    x, y, parts = _token_data()
    api = _mk(build_federated_arrays(x, y, parts, B))
    base0 = _snap(api.base)
    losses = api.train_rounds_on_device(5)
    assert len(np.asarray(losses)) == 5
    assert np.isfinite(np.asarray(losses)).all()
    _trees_equal(base0, api.base)


def test_windowed_steady_state_zero_recompiles():
    """The acceptance pin: zero steady-state recompiles at a
    non-dividing W."""
    from fedml_tpu.obs.sanitizer import sanitized

    x, y, parts = _token_data(per=16)
    api = FedAdapterAPI(_model(), FederatedStore(x, y, parts, batch_size=B),
                        None, _cfg(rounds=32), loss_fn=LOSS)
    api.train_rounds_windowed(9, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(9, start_round=9, window=4)
    assert len(losses) == 9
    assert rep.compiles == 0


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_checkpoint_restore_mid_window_with_personal_stacks():
    """Checkpoint at a window boundary: the adapter net AND the
    personalized per-client adapter stacks restore bit-equal, and the
    continued run equals the uninterrupted host loop exactly."""
    from fedml_tpu.obs.checkpoint import (CheckpointManager, restore_run,
                                          save_run)

    x, y, parts = _token_data(per=12)

    def mk():
        return FedAdapterAPI(_model(),
                             FederatedStore(x, y, parts, batch_size=B),
                             None, _cfg(rounds=8), loss_fn=LOSS)

    host = mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(8)]

    a = mk()
    lb = a.train_rounds_windowed(4, window=4)
    a.personalize_cohort([0, 2, 4])  # populate personal stacks pre-save
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td + "/ckpt")
        save_run(mgr, a, 3)  # after round 3 = the window boundary
        b = mk()
        b.personal_store()  # template for the extra-state restore
        nxt = restore_run(mgr, b)
        mgr.close()
    assert nxt == 4
    _trees_equal(a.net.params, b.net.params)
    np.testing.assert_array_equal(
        a.personal_store().state_dict()["personal_vecs"],
        b.personal_store().state_dict()["personal_vecs"])
    np.testing.assert_array_equal(
        a.personal_store().state_dict()["personal_seen"],
        b.personal_store().state_dict()["personal_seen"])
    lb += b.train_rounds_windowed(4, start_round=4, window=4)
    np.testing.assert_array_equal(la, lb)
    _trees_equal(host.net.params, b.net.params)


def test_personal_store_memmap_spill(tmp_path):
    """PersonalAdapterStore spills to a memmap; unseen rows gather as
    the provided default; scatter/gather round-trips; a rank-mismatched
    checkpoint refuses."""
    from fedml_tpu.models.adapter import PersonalAdapterStore

    tpl = {"a": np.arange(4, dtype=np.float32),
           "m": {"lora_x_a": np.ones((2, 2), np.float32)}}
    st = PersonalAdapterStore(10, tpl, spill_dir=str(tmp_path))
    assert st.memmapped and st.dim == 8
    default = jax.tree.map(lambda l: l * 2.0, tpl)
    got = st.gather([3, 7], default)
    np.testing.assert_array_equal(got[0], st.vec_of(default))
    vec = np.arange(8, dtype=np.float32)
    st.scatter([3], vec[None])
    got = st.gather([3, 7], default)
    np.testing.assert_array_equal(got[0], vec)
    np.testing.assert_array_equal(got[1], st.vec_of(default))
    tree = st.tree_of(vec)
    assert jax.tree.structure(tree) == jax.tree.structure(tpl)
    other = PersonalAdapterStore(10, {"a": np.zeros(3, np.float32)})
    with pytest.raises(ValueError, match="shape"):
        other.load_state_dict(st.state_dict())


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_personalization_positive_on_dialect_train_shards():
    """The fast-lane personalization mechanics pin: on the dialect law
    the per-client finetuned adapters beat the global adapters on the
    clients' OWN shards (the held-out generalization delta is the slow
    bench/REPRO pin)."""
    from fedml_tpu.data.synthetic import make_stackoverflow_nwp

    x, y, parts = make_stackoverflow_nwp(
        12, seq_len=T, vocab=V, seed=0, law="dialect", kgroup=4,
        active_tokens=16, count_scale=4)
    fed = build_federated_arrays(x, y, parts, B)
    cfg = _cfg(n=12, cpr=6, rounds=6, epochs=2, lr=0.3)
    api = FedAdapterAPI(_model(rank=8, scope="all"), fed, None, cfg,
                        loss_fn=LOSS, personal_interp=1.0)
    api.train()
    for p in range(4):
        api.personalize_cohort(np.arange(12), seed=p)
    m = api.evaluate_personalized(fed)
    assert m["personalized_delta"] > 0.02, m


@pytest.mark.slow  # adam pretrain + fed rounds + 10 personalize passes
def test_personalization_heldout_delta_dialect_pin():
    """The REPRO.md NWP personalization pin: on the dialect law, per-
    client personalized adapter stacks beat the global adapters on
    HELD-OUT per-client data (calibrated 2026-08-04: delta +0.066 at
    this config; asserted > 0.03). The base is adam-pretrained on the
    pooled train split — LoRA is a finetuning method, a random frozen
    base has nothing for rank-r adapters to steer."""
    import optax

    from fedml_tpu.data.synthetic import make_stackoverflow_nwp

    V2, T2, B2, N2 = 256, 8, 8, 24
    law = dict(seq_len=T2, vocab=V2, law="dialect", kgroup=8,
               active_tokens=32, count_scale=8)
    x, y, parts = make_stackoverflow_nwp(N2, seed=0, **law)
    xh, yh, ph = make_stackoverflow_nwp(N2, seed=1, **law)

    def mk(rank, scope="all"):
        return create_model("transformer_lm", vocab_size=V2, d_model=32,
                            n_heads=2, n_layers=2, max_len=T2,
                            adapter_rank=rank, adapter_scope=scope)

    fns = model_fns(mk(0))
    net = fns.init(jax.random.PRNGKey(0), jnp.zeros((1, T2), jnp.int32))
    opt = optax.adam(3e-3)

    def loss(params, xb, yb):
        logits, _ = fns.apply(NetState(params, net.model_state), xb)
        return LOSS(logits, yb).mean()

    @jax.jit
    def step(params, ost, xb, yb):
        l, g = jax.value_and_grad(loss)(params, xb, yb)
        u, ost = opt.update(g, ost)
        return optax.apply_updates(params, u), ost, l

    params, ost = net.params, opt.init(net.params)
    rng = np.random.RandomState(0)
    xs, ys = jnp.asarray(x), jnp.asarray(y)
    for _ in range(500):
        idx = rng.randint(0, len(x), 32)
        params, ost, _ = step(params, ost, xs[idx], ys[idx])

    fed = build_federated_arrays(x, y, parts, B2)
    fedh = build_federated_arrays(xh, yh, ph, B2)
    cfg = FedConfig(client_num_in_total=N2, client_num_per_round=8,
                    comm_round=8, epochs=2, batch_size=B2, lr=0.3, seed=0,
                    frequency_of_the_test=1000)
    api = FedAdapterAPI(mk(8), fed, None, cfg, loss_fn=LOSS,
                        base_params=jax.tree.map(np.asarray, params),
                        personal_interp=1.0)
    api.train()
    for p in range(10):
        api.personalize_cohort(np.arange(N2), seed=p)
    m = api.evaluate_personalized(fedh)
    assert m["personalized_delta"] > 0.03, m
    assert m["personal_accuracy"] > m["global_local_accuracy"]


# ------------------------------------------- message-passing delta tiers --

def _dist_setup(rank=4, n=4, cpr=2, rounds=4, **cfg_kw):
    x, y, parts = _token_data(n_clients=n)
    fed = build_federated_arrays(x, y, parts, B)
    cfg = _cfg(n=n, cpr=cpr, rounds=rounds, adapter_rank=rank, **cfg_kw)
    return _model(rank=rank), fed, cfg


def test_fedbuff_adapter_topk_int8_delta_drill():
    """The composed drill: FedBuff ships ADAPTER-only topk+int8 EF
    deltas over the loopback tensor wire — zero refusals, bytes/upload
    far below the dense tree, frozen base bitwise-identical to the
    deterministic init."""
    from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed

    model, fed, cfg = _dist_setup()
    srv = FedML_FedBuff_distributed(model, fed, None, cfg,
                                    wire_codec="topk0.25+int8",
                                    loopback_wire="tensor", buffer_k=2,
                                    loss_fn=LOSS)
    h = srv.final_health
    assert srv.version == cfg.comm_round
    assert h["codec_refusals"] == 0
    uploads = len(srv.arrival_log)
    dense_nbytes = 4 * param_count(
        model_fns(_model(rank=0)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)).params)
    assert h["bytes_rx"] / max(uploads, 1) < 0.25 * dense_nbytes
    # Frozen base: bitwise-identical to the deterministic fresh init.
    ref = adapter_model_fns(_model(rank=4))
    ref.init(jax.random.PRNGKey(cfg.seed), jnp.zeros((1, T), jnp.int32))
    _trees_equal(ref.holder["base"], srv.adapter_holder["base"])


def test_sync_tier_accepts_adapter_delta_frames():
    """The promoted delta capability: the SYNC server's anchor-based
    decode accepts adapter codec frames (was FedBuff-only)."""
    from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed

    model, fed, cfg = _dist_setup(rounds=3)
    agg = FedML_FedAvg_distributed(model, fed, None, cfg,
                                   wire_codec="topk0.25+int8",
                                   loopback_wire="tensor", loss_fn=LOSS)
    assert agg.final_health["codec_refusals"] == 0
    assert agg.final_health["bytes_rx"] > 0
    ref = adapter_model_fns(_model(rank=4))
    ref.init(jax.random.PRNGKey(cfg.seed), jnp.zeros((1, T), jnp.int32))
    _trees_equal(ref.holder["base"], agg.adapter_holder["base"])


def test_sync_adapter_bitequal_to_simulator_without_codec():
    """Plain tensor-wire sync federation over the adapter tree matches
    the mechanics (full-model adapter uploads, no codec): zero refusals
    and a trained adapter tree."""
    from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed

    model, fed, cfg = _dist_setup(rounds=2)
    agg = FedML_FedAvg_distributed(model, fed, None, cfg, loss_fn=LOSS)
    assert agg.final_health["codec_refusals"] == 0
    leaves = jax.tree.leaves(agg.net.params)
    assert leaves and all(np.isfinite(np.asarray(l)).all() for l in leaves)


def test_delta_sender_refuses_delta_ignorant_peer():
    """require_delta_peer: a FedBuff (delta) client whose first
    assignment lacks DELTA_OK_KEY refuses loudly instead of letting the
    server mis-fold its deltas as full models."""
    from fedml_tpu.comm import codec as wire_codec

    with pytest.raises(ValueError, match="delta-ignorant"):
        wire_codec.require_delta_peer(None, peer="server")
    with pytest.raises(ValueError, match="delta-ignorant"):
        wire_codec.require_delta_peer(False, peer="server")
    wire_codec.require_delta_peer(True, peer="server")  # no raise


def test_async_server_refuses_mismatched_delta_stamp():
    """A delta-stamped upload at the pure-async (full-model) server is
    refused + the worker evict-and-released — never mixed as a full
    model. Fake-clock protocol-test pattern."""
    from fedml_tpu.algos.fedasync import (MSG_ARG_KEY_MODEL_VERSION,
                                          MSG_ARG_KEY_TASK_SEQ,
                                          FedAsyncServerManager)
    from fedml_tpu.algos.fedavg_distributed import (
        MSG_ARG_KEY_MODEL_PARAMS, MSG_ARG_KEY_NUM_SAMPLES,
        MSG_TYPE_C2S_SEND_MODEL_TO_SERVER)
    from fedml_tpu.comm import codec as wire_codec
    from fedml_tpu.comm.loopback import LoopbackNetwork
    from fedml_tpu.comm.message import Message

    class A:
        pass

    a = A()
    a.chaos = None
    a.network = LoopbackNetwork(3)
    net0 = {"w": np.zeros(4, np.float32)}
    cfg = _cfg(n=2, cpr=2, rounds=4)
    srv = FedAsyncServerManager(a, net0, cfg, 3)
    srv.register_message_receive_handlers()
    assert srv._accepts_delta_frames is False
    m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 1, 0)
    m.add(MSG_ARG_KEY_MODEL_PARAMS, {"w": np.ones(4, np.float32)})
    m.add(MSG_ARG_KEY_NUM_SAMPLES, 4)
    m.add(MSG_ARG_KEY_MODEL_VERSION, 0)
    m.add(MSG_ARG_KEY_TASK_SEQ, 0)
    m.add(wire_codec.DELTA_KEY, True)  # delta against a full-model tier
    srv.handle_upload(m)
    assert srv.codec_refusals == 1
    assert srv.version == 0  # never mixed
    np.testing.assert_array_equal(np.asarray(srv.net["w"]),
                                  np.zeros(4, np.float32))
    assert 1 not in srv._members  # evict-and-released


def test_fedbuff_server_refuses_full_model_stamp():
    """The dual: a full-model-stamped upload at the buffered (delta)
    server refuses instead of buffering a full model as a delta."""
    from fedml_tpu.algos.fedasync import (MSG_ARG_KEY_MODEL_VERSION,
                                          MSG_ARG_KEY_TASK_SEQ)
    from fedml_tpu.algos.fedbuff import FedBuffServerManager
    from fedml_tpu.algos.fedavg_distributed import (
        MSG_ARG_KEY_MODEL_PARAMS, MSG_ARG_KEY_NUM_SAMPLES,
        MSG_TYPE_C2S_SEND_MODEL_TO_SERVER)
    from fedml_tpu.comm import codec as wire_codec
    from fedml_tpu.comm.loopback import LoopbackNetwork
    from fedml_tpu.comm.message import Message

    class A:
        pass

    a = A()
    a.chaos = None
    a.network = LoopbackNetwork(3)
    net0 = {"w": np.zeros(4, np.float32)}
    srv = FedBuffServerManager(a, net0, _cfg(n=2, cpr=2, rounds=4), 3,
                               buffer_k=2)
    srv.register_message_receive_handlers()
    assert srv._accepts_delta_frames is True
    m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 1, 0)
    m.add(MSG_ARG_KEY_MODEL_PARAMS, {"w": np.ones(4, np.float32)})
    m.add(MSG_ARG_KEY_NUM_SAMPLES, 4)
    m.add(MSG_ARG_KEY_MODEL_VERSION, 0)
    m.add(MSG_ARG_KEY_TASK_SEQ, 0)
    m.add(wire_codec.DELTA_KEY, False)
    srv.handle_upload(m)
    assert srv.codec_refusals == 1
    assert srv._count == 0  # never buffered


def test_async_full_model_adapter_uploads():
    """Pure async + adapter: FULL adapter-tree uploads (stamped
    delta=False) flow through the full-model mix unchanged."""
    from fedml_tpu.algos.fedasync import FedML_FedAsync_distributed

    model, fed, cfg = _dist_setup(rounds=4)
    srv = FedML_FedAsync_distributed(model, fed, None, cfg, loss_fn=LOSS)
    assert srv.version >= cfg.comm_round
    assert srv.final_health["codec_refusals"] == 0


# ------------------------------------------------ capability + matrix ----

def test_capability_record_all_tiers():
    from fedml_tpu.algos.capability import record_for

    rec = record_for(FedAdapterAPI)
    assert rec.protocol == "round"
    assert rec.fused and rec.windowed and rec.on_device
    assert rec.streaming


def test_support_matrix_has_fedadapter_row():
    from fedml_tpu.algos.capability import render_matrix

    row = [l for l in render_matrix().splitlines()
           if l.startswith("| FedAdapter ")]
    assert row and row[0].count("✓") == 3


# ---------------------------------------------------- driver rejections --

def test_mesh_and_layout_refusals():
    x, y, parts = _token_data()
    fed = build_federated_arrays(x, y, parts, B)
    with pytest.raises(NotImplementedError, match="compute_layout"):
        FedAdapterAPI(_model(), fed, None, _cfg(compute_layout="auto"),
                      loss_fn=LOSS)
    with pytest.raises(ValueError, match="personal_interp"):
        FedAdapterAPI(_model(), fed, None, _cfg(), loss_fn=LOSS,
                      personal_interp=1.5)


def test_bf16_client_step_trains_float32_adapters():
    """``cfg.client_step_dtype="bf16"`` (refused until PR 32): the trainer
    applies the bf16 twin merged with the SAME held base; the adapters, their
    gradients and the aggregation stay float32, the base stays as it was."""
    x, y, parts = _token_data()
    fed = build_federated_arrays(x, y, parts, B)
    api = FedAdapterAPI(_model(), fed, None, _cfg(client_step_dtype="bf16"),
                        loss_fn=LOSS)
    base0, adapters0 = _snap(api.base), _snap(api.net.params)
    assert np.isfinite(api.train_one_round(0)["train_loss"])
    _trees_equal(base0, api.base)
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, np.asarray(b)),
                         adapters0, api.net.params)
    assert any(jax.tree.leaves(moved))
    assert {a.dtype for a in jax.tree.leaves(api.net.params)} == {
        np.dtype("float32")}


def test_driver_flag_rejection_matrix():
    """--adapter_rank/--adapter_scope refuse across the specialty
    drivers (cross-silo, centralized, the non-async main_extra
    algorithms, non-FedAdapter run.py algorithms) per the PR 4/14
    convention."""
    from fedml_tpu.exp.args import parse_args, reject_adapter_flags

    args = parse_args(["--adapter_rank", "4"])
    for driver in ("the cross-silo pipeline", "the centralized baseline",
                   "FedGAN", "FedAvg"):
        with pytest.raises(SystemExit, match="adapter"):
            reject_adapter_flags(args, driver)
    # scope alone (non-default) refuses too
    args2 = parse_args(["--adapter_scope", "all"])
    with pytest.raises(SystemExit, match="adapter_scope"):
        reject_adapter_flags(args2, "FedAvg")
    # defaults pass silently
    reject_adapter_flags(parse_args([]), "FedAvg")


def test_main_extra_rejects_adapter_on_specialty_loops():
    from fedml_tpu.exp import main_extra

    with pytest.raises(SystemExit, match="adapter"):
        main_extra.main(["--algorithm", "FedGAN", "--adapter_rank", "2"])
    with pytest.raises(SystemExit, match="transformer_lm"):
        main_extra.main(["--algorithm", "FedBuff", "--adapter_rank", "2",
                         "--model", "cnn"])


def test_run_py_fedadapter_guards():
    from fedml_tpu.exp.args import parse_args
    from fedml_tpu.exp.run import run

    with pytest.raises(SystemExit, match="adapter_rank > 0"):
        run(parse_args(["--model", "transformer_lm",
                        "--dataset", "stackoverflow_nwp"]), "FedAdapter")
    with pytest.raises(SystemExit, match="transformer_lm"):
        run(parse_args(["--model", "cnn", "--dataset", "femnist",
                        "--adapter_rank", "2"]), "FedAdapter")
    with pytest.raises(SystemExit, match="sequence dataset"):
        run(parse_args(["--model", "transformer_lm", "--dataset", "femnist",
                        "--adapter_rank", "2"]), "FedAdapter")


# ------------------------------------------------------- the data law ----

def test_dialect_law_properties():
    """Counts share the uniform law's stream; dialects live on a shared
    token subset; a held-out seed shares the dialect tables; uniform
    default is bit-identical to the historical draw."""
    from fedml_tpu.data.synthetic import make_stackoverflow_shard

    xu, yu, cu = make_stackoverflow_shard(40, 12, 512, seed=9)
    rng = np.random.RandomState(9)
    counts0 = 1 + (rng.pareto(1.5, 40) * 4).astype(np.int64).clip(0, 63)
    tot = int(counts0.sum())
    x0 = rng.randint(1, 512, (tot, 12)).astype(np.int32)
    np.testing.assert_array_equal(cu, counts0)
    np.testing.assert_array_equal(xu, x0)
    np.testing.assert_array_equal(yu, np.roll(x0, -1, axis=1))

    kw = dict(law="dialect", kgroup=4, active_tokens=16)
    xd, yd, cd = make_stackoverflow_shard(40, 12, 512, seed=9, **kw)
    np.testing.assert_array_equal(cd, counts0)  # shared count law
    assert len(np.unique(xd)) <= 16
    xh, _, _ = make_stackoverflow_shard(40, 12, 512, seed=10, **kw)
    assert set(np.unique(xh).tolist()) <= set(np.unique(xd).tolist())
    np.testing.assert_array_equal(yd, np.roll(
        np.concatenate([xd, yd[:, -1:]], axis=1), -1, axis=1)[:, :-1])
    # count_scale multiplies mass, same shape
    _, _, cs = make_stackoverflow_shard(40, 12, 512, seed=9,
                                        count_scale=3, **kw)
    np.testing.assert_array_equal(cs, counts0 * 3)
    # group_offset shifts dialect assignment with global client ids
    xg, _, cg = make_stackoverflow_shard(1, 12, 512, seed=9,
                                         group_offset=2, **kw)
    assert len(xg) == cg.sum()
    with pytest.raises(ValueError, match="unknown token law"):
        make_stackoverflow_shard(4, 12, 512, law="zipf")


# ------------------------- a model's own collection rides the round (PR 34) --

import flax.linen as nn  # noqa: E402


class _CountingLM(nn.Module):
    """A frozen embedding and head with one low-rank pair between them, and
    a ``counters`` collection: the tokens each call saw, by parity of id."""

    @nn.compact
    def __call__(self, ids, train: bool = False):
        embed = self.param("embed", nn.initializers.normal(0.1), (V, 8))
        head = self.param("head", nn.initializers.normal(0.1), (8, V))
        a = self.param("lora_mid_a", nn.initializers.normal(0.1), (8, 2))
        b = self.param("lora_mid_b", nn.initializers.normal(0.1), (2, 8))
        x = embed[ids]
        if self.is_mutable_collection("counters"):
            seen = self.variable("counters", "tokens_by_parity",
                                 lambda: jnp.zeros(2, jnp.float32))
            if not self.is_initializing():
                seen.value = seen.value + jnp.stack([
                    jnp.sum(ids % 2 == 0), jnp.sum(ids % 2 == 1)]).astype(
                        jnp.float32)
        return (x + x @ a @ b) @ head


@pytest.mark.parametrize("group", [0, 1])
def test_the_round_carries_a_models_counters_beside_the_adapters(group):
    """A collection that is no part of the base rides ``NetState.
    model_state`` through the adapter round, whole cohort or a client at a
    time: each round adds the cohort's weighted mean of what a client's
    local steps counted; the base stays bit for bit, and the pretrained
    (``base_params``) path makes the collection too."""
    x, y, parts = _token_data(n_clients=4, per=8)
    train = build_federated_arrays(x, y, parts, B)
    cfg = _cfg(n=4, cpr=4, client_group_size=group)
    api = FedAdapterAPI(_CountingLM(), train, None, cfg, loss_fn=LOSS)
    assert set(api.net.model_state) == {"counters"}
    np.testing.assert_array_equal(
        api.net.model_state["counters"]["tokens_by_parity"], [0, 0])
    base0 = _snap(api.base)
    api.train_one_round(0)
    api.train_one_round(1)
    _trees_equal(base0, api.base)
    seen = np.asarray(api.net.model_state["counters"]["tokens_by_parity"])
    # every client holds 8 sequences of T tokens and trains them once a round
    assert seen.sum() == pytest.approx(2 * 8 * T)
    even = np.mean([(x[parts[c]] % 2 == 0).sum() for c in range(4)])
    assert seen[0] == pytest.approx(2 * even)
    pretrained = FedAdapterAPI(_CountingLM(), train, None, cfg, loss_fn=LOSS,
                               base_params=api.base)
    assert pretrained.net.model_state["counters"][
        "tokens_by_parity"].shape == (2,)
    _trees_equal(pretrained.net.params, FedAdapterAPI(
        _CountingLM(), train, None, cfg, loss_fn=LOSS).net.params)
