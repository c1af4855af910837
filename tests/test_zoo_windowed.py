"""Whole-zoo carry capability records: every pure-server-state algorithm
rides fused + windowed execution, pinned bit-equal to its
host loop; excluded algorithms refuse with the record-derived reason;
the EXECUTION.md support matrix is generated from the records and
drift-tested.

The PR-3 test pattern per converted algorithm: windowed-vs-host equality
(``assert_array_equal``) at a NON-dividing window on power-law counts
(the window-max bucket forcing path runs), a mesh variant where the
algorithm shards, a checkpoint at a window boundary, and a sanitized
zero-recompile pin."""

import jax
import numpy as np
import pytest

from fedml_tpu.algos.capability import (
    matrix_block,
    record_for,
    refusal,
    zoo_records,
)
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedac import FedAcAPI, ServerAvgAPI
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.algos.feddyn import FedDynAPI
from fedml_tpu.algos.fednova import FedNovaAPI
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.models.lr import LogisticRegression


def _power_law(seed=0, n_clients=12, d=6):
    rng = np.random.RandomState(seed)
    counts = np.concatenate([[600], rng.randint(20, 90, n_clients - 1)])
    tot = int(counts.sum())
    x = rng.randn(tot, d).astype(np.float32)
    y = (x @ rng.randn(d) > 0).astype(np.int32)
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1])
             for c in range(n_clients)}
    return x, y, parts


def _cfg(n, cpr, rounds, batch=16, **kw):
    kw.setdefault("lr", 0.3)
    kw.setdefault("epochs", 1)
    kw.setdefault("frequency_of_the_test", 1000)
    return FedConfig(client_num_in_total=n, client_num_per_round=cpr,
                     comm_round=rounds, batch_size=batch, **kw)


def _assert_trees_equal(a, b):
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def _run_windowed_vs_host(mk, rounds=9, window=4, state_of=None):
    """Host loop vs windowed at a non-dividing window; returns the two
    APIs for extra assertions."""
    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(rounds)]
    lb = win.train_rounds_windowed(rounds, window=window)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, win.net.params)
    if state_of is not None:
        _assert_trees_equal(state_of(host), state_of(win))
    return host, win


# --------------------------------------------------------------- FedDyn --

def _mk_feddyn(mesh=None, n=12, cpr=4, rounds=9, seed=0):
    x, y, parts = _power_law(seed=seed, n_clients=n)

    def mk():
        return FedDynAPI(LogisticRegression(num_classes=2),
                         FederatedStore(x, y, parts, batch_size=16), None,
                         _cfg(n, cpr, rounds, lr=0.1), alpha=0.05,
                         mesh=mesh)

    return mk


@pytest.mark.slow  # >7 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_feddyn_bit_equal():
    """FedDyn's "custom" carry (server h + client correction stack)
    rides the scan bit-equal — params, h, AND the correction stack."""
    _run_windowed_vs_host(
        _mk_feddyn(),
        state_of=lambda a: (a.server_h, a.client_grads))


@pytest.mark.slow  # >5.4 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_feddyn_mesh_bit_equal():
    from fedml_tpu.parallel.mesh import client_mesh

    mk = _mk_feddyn(mesh=client_mesh(8), n=16, cpr=8, rounds=6, seed=2)
    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, win.net.params)
    _assert_trees_equal(host.client_grads, win.client_grads)


def test_feddyn_streaming_matches_resident():
    """The conversion's streaming seam: a store-backed FedDyn host loop
    trains bit-equal to the resident-layout host loop."""
    from fedml_tpu.data.batching import build_federated_arrays

    x, y, parts = _power_law(seed=8)

    def mk(fed):
        return FedDynAPI(LogisticRegression(num_classes=2), fed, None,
                         _cfg(12, 4, 4, lr=0.1), alpha=0.05)

    res = mk(build_federated_arrays(x, y, parts, batch_size=16))
    st = mk(FederatedStore(x, y, parts, batch_size=16))
    la = [res.train_one_round(r)["train_loss"] for r in range(4)]
    lb = [st.train_one_round(r)["train_loss"] for r in range(4)]
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(res.net.params, st.net.params)
    _assert_trees_equal(res.client_grads, st.client_grads)


def test_windowed_feddyn_checkpoint_restore_mid_run(tmp_path):
    """Checkpoint at a window boundary: h + the correction stack are
    committed carry, so save → fresh → restore → continue equals one
    uninterrupted host run exactly."""
    from fedml_tpu.obs.checkpoint import (CheckpointManager, restore_run,
                                          save_run)

    mk = _mk_feddyn(rounds=8)
    host = mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(8)]

    a = mk()
    lb = a.train_rounds_windowed(4, window=4)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    save_run(mgr, a, 3)  # after round 3 = the window boundary
    b = mk()
    nxt = restore_run(mgr, b)
    mgr.close()
    assert nxt == 4
    lb += b.train_rounds_windowed(4, start_round=4, window=4)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, b.net.params)
    _assert_trees_equal(host.server_h, b.server_h)
    _assert_trees_equal(host.client_grads, b.client_grads)


def test_windowed_feddyn_steady_state_sanitized():
    """Zero steady-state recompiles for the converted "custom" carry,
    non-dividing window included (the remainder round rides the SAME
    fused step program as the scan body)."""
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(4)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}
    api = FedDynAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=8), None,
                    _cfg(12, 4, 32, batch=8, lr=0.1), alpha=0.05)
    api.train_rounds_windowed(9, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(9, start_round=9, window=4)
    assert len(losses) == 9
    assert rep.compiles == 0


# -------------------------------------------------------------- FedNova --

def test_windowed_fednova_bit_equal():
    """FedNova's τ-normalized weights + γ ride the scanned aux slot —
    the whole normalized-averaging round is one fused program."""
    x, y, parts = _power_law(seed=5)

    def mk():
        return FedNovaAPI(LogisticRegression(num_classes=2),
                          FederatedStore(x, y, parts, batch_size=16), None,
                          _cfg(12, 4, 9, epochs=2))

    _run_windowed_vs_host(mk)


@pytest.mark.slow  # >5.4 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_windowed_fednova_mesh_bit_equal():
    from fedml_tpu.parallel.mesh import client_mesh

    x, y, parts = _power_law(seed=6, n_clients=16)
    mesh = client_mesh(8)

    def mk():
        return FedNovaAPI(LogisticRegression(num_classes=2),
                          FederatedStore(x, y, parts, batch_size=16), None,
                          _cfg(16, 8, 6), mesh=mesh)

    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(6)]
    lb = win.train_rounds_windowed(6, window=3)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, win.net.params)


def test_fednova_on_device_refusal_names_aux():
    """Record-derived refusal: per-round host-computed aux operands have
    no slot in the on-device scan."""
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(0)
    x = rng.randn(64, 6).astype(np.float32)
    y = (rng.rand(64) > 0.5).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(64, 4), 16)
    api = FedNovaAPI(LogisticRegression(num_classes=2), fed, None,
                     _cfg(4, 4, 2))
    with pytest.raises(NotImplementedError, match="aux"):
        api.train_rounds_on_device(2)


# ---------------------------------------------------------------- Ditto --

@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_windowed_ditto_bit_equal():
    """Ditto's personal-model stack is the carry: global params AND all
    personal models bit-equal across tiers (repeat clients inside one
    window see their own earlier personal update)."""
    from fedml_tpu.algos.ditto import DittoAPI

    x, y, parts = _power_law(seed=7)

    def mk():
        return DittoAPI(LogisticRegression(num_classes=2),
                        FederatedStore(x, y, parts, batch_size=16), None,
                        _cfg(12, 4, 9), lam=0.2)

    host, win = _run_windowed_vs_host(
        mk, state_of=lambda a: a.personal_nets)
    # The personalized eval works on the streaming layout too.
    m = win.evaluate_personalized()
    assert 0.0 <= m["personal_accuracy"] <= 1.0


# ---------------------------------------------------------------- FedBN --

class _LNNet:
    def __new__(cls, num_classes=3):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                x = x.reshape((x.shape[0], -1))
                x = nn.Dense(8)(x)
                x = nn.LayerNorm()(x)
                return nn.Dense(num_classes)(x)

        return Net()


def test_windowed_fedbn_bit_equal():
    """FedBN's client norm store + state stack ride the scan bit-equal
    (masked gather/scatter of the norm leaves inside the step)."""
    from fedml_tpu.algos.fedbn import FedBNAPI

    rng = np.random.RandomState(3)
    counts = np.array([120, 30, 50, 20, 70, 40])
    edges = np.concatenate([[0], np.cumsum(counts)])
    x = rng.randn(counts.sum(), 6).astype(np.float32)
    y = rng.randint(0, 3, counts.sum()).astype(np.int32)
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(6)}

    def mk():
        return FedBNAPI(_LNNet(), FederatedStore(x, y, parts, batch_size=16),
                        None, _cfg(6, 3, 7, lr=0.1))

    host, win = _run_windowed_vs_host(
        mk, rounds=7, window=3,
        state_of=lambda a: (a.local_norms, a.local_state))
    m = win.evaluate_personalized()  # streaming personalized eval
    assert 0.0 <= m["personal_accuracy"] <= 1.0


# --------------------------------------------------------------- FedGAN --

@pytest.mark.slow  # MNIST-GAN compile ~15 s on the 2-core box
def test_windowed_fedgan_bit_equal():
    """FedGAN is a FedAvg-family record now: the adversarial local step
    is prefix-stable (per-step noise keys fold_in on the step index), so
    the windowed scan is bit-equal to the host loop."""
    from fedml_tpu.algos.fedgan import FedGanAPI
    from fedml_tpu.models.gan import MNISTGan

    rng = np.random.RandomState(1)
    counts = np.array([40, 16, 24, 16])
    edges = np.concatenate([[0], np.cumsum(counts)])
    x = np.tanh(rng.randn(int(counts.sum()), 28, 28, 1)).astype(np.float32)
    y = np.zeros((len(x),), np.int32)
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(4)}

    def mk():
        return FedGanAPI(MNISTGan(),
                         FederatedStore(x, y, parts, batch_size=8),
                         _cfg(4, 2, 5, batch=8, lr=2e-4))

    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(5)]
    lb = win.train_rounds_windowed(5, window=2)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, win.net.params)


# --------------------------------------------------------------- FedNAS --

@pytest.mark.slow  # DARTS compile ~40 s on the 2-core box
def test_windowed_fednas_bit_equal():
    """FedNAS as a FedAvg-family record: the bilevel step's train/valid
    split is MASK-AWARE (cut at the true step count), so a cohort forced
    onto a larger window-max bucket trains identically — windowed ==
    host across mixed buckets."""
    from fedml_tpu.algos.fednas import FedNASAPI
    from fedml_tpu.models.darts import DartsNetwork

    rng = np.random.RandomState(0)
    counts = np.array([96, 32, 48, 64])  # batch 8 → buckets 16/4/8/8
    edges = np.concatenate([[0], np.cumsum(counts)])
    x = (rng.randn(counts.sum(), 8, 8, 3) * 0.1).astype(np.float32)
    y = rng.randint(0, 4, counts.sum()).astype(np.int32)
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(4)}

    def mk():
        return FedNASAPI(
            DartsNetwork(c=4, layers=1, steps=2, multiplier=2,
                         num_classes=4),
            FederatedStore(x, y, parts, batch_size=8), None,
            _cfg(4, 2, 5, batch=8, lr=0.05), arch_lr=3e-3)

    host, win = mk(), mk()
    la = [host.train_one_round(r)["train_loss"] for r in range(5)]
    lb = win.train_rounds_windowed(5, window=2)
    np.testing.assert_array_equal(la, lb)
    _assert_trees_equal(host.net.params, win.net.params)


# ------------------------------------------------- FedAc / ServerAvg -----

def _mk_simple(cls, seed=9, **kw):
    x, y, parts = _power_law(seed=seed)

    def mk():
        return cls(LogisticRegression(num_classes=2),
                   FederatedStore(x, y, parts, batch_size=16), None,
                   _cfg(12, 4, 9), **kw)

    return mk


def test_windowed_fedac_bit_equal():
    _run_windowed_vs_host(_mk_simple(FedAcAPI),
                          state_of=lambda a: a._fedac_state)


def test_windowed_server_avg_bit_equal():
    _run_windowed_vs_host(_mk_simple(ServerAvgAPI, avg_coef=0.5),
                          state_of=lambda a: a._savg_state)


def test_fedac_gamma_one_is_fedavg():
    """γ=1 collapses the acceleration recursion to plain FedAvg."""
    a = _mk_simple(FedAvgAPI)()
    b = _mk_simple(FedAcAPI, gamma=1.0)()
    la = [a.train_one_round(r)["train_loss"] for r in range(5)]
    lb = [b.train_one_round(r)["train_loss"] for r in range(5)]
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for pa, pb in zip(jax.tree.leaves(a.net.params),
                      jax.tree.leaves(b.net.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-5, atol=1e-6)


def test_server_avg_beta_zero_is_fedavg():
    a = _mk_simple(FedAvgAPI)()
    b = _mk_simple(ServerAvgAPI, avg_coef=0.0)()
    la = [a.train_one_round(r)["train_loss"] for r in range(5)]
    lb = [b.train_one_round(r)["train_loss"] for r in range(5)]
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)


def test_fedac_on_device_bit_equal_full_participation():
    """FedAc's (x, x_ag) sequences thread the on-device scan's carry —
    bit-equal to the host loop at full participation."""
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(2)
    x = rng.randn(320, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(320, 4), 16)
    cfg = _cfg(4, 4, 5)
    h = FedAcAPI(LogisticRegression(num_classes=2), fed, None, cfg)
    hl = [h.train_one_round(r)["train_loss"] for r in range(5)]
    d = FedAcAPI(LogisticRegression(num_classes=2), fed, None, cfg)
    dl = d.train_rounds_on_device(5)
    np.testing.assert_allclose(hl, np.asarray(dl), rtol=1e-6, atol=1e-6)
    _assert_trees_equal(h.net.params, d.net.params)
    _assert_trees_equal(h._fedac_state, d._fedac_state)


def test_windowed_fedac_steady_state_sanitized():
    """Zero steady-state recompiles for the accelerated carry."""
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(5)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}
    api = FedAcAPI(LogisticRegression(num_classes=2),
                   FederatedStore(x, y, parts, batch_size=8), None,
                   _cfg(12, 4, 32, batch=8))
    api.train_rounds_windowed(8, start_round=0, window=4)  # warmup
    with sanitized() as rep:
        losses = api.train_rounds_windowed(8, start_round=8, window=4)
    assert len(losses) == 8
    assert rep.compiles == 0


def test_windowed_converted_zoo_steady_state_sanitized():
    """Zero steady-state recompiles for the remaining converted records
    (FedNova's scanned aux, Ditto's personal stack, FedBN's norm store)
    on uniform buckets — FedDyn and FedAc have their own pins above."""
    from fedml_tpu.algos.ditto import DittoAPI
    from fedml_tpu.algos.fedbn import FedBNAPI
    from fedml_tpu.obs.sanitizer import sanitized

    rng = np.random.RandomState(6)
    x = rng.randn(12 * 32, 6).astype(np.float32)
    y = rng.randint(0, 3, 12 * 32).astype(np.int32)
    parts = {c: np.arange(c * 32, (c + 1) * 32) for c in range(12)}

    def run(make):
        api = make()
        api.train_rounds_windowed(8, start_round=0, window=4)  # warmup
        with sanitized() as rep:
            losses = api.train_rounds_windowed(8, start_round=8, window=4)
        assert len(losses) == 8
        assert rep.compiles == 0, type(api).__name__

    run(lambda: FedNovaAPI(LogisticRegression(num_classes=3),
                           FederatedStore(x, y, parts, batch_size=8), None,
                           _cfg(12, 4, 32, batch=8)))
    run(lambda: DittoAPI(LogisticRegression(num_classes=3),
                         FederatedStore(x, y, parts, batch_size=8), None,
                         _cfg(12, 4, 32, batch=8)))
    run(lambda: FedBNAPI(_LNNet(), FederatedStore(x, y, parts, batch_size=8),
                         None, _cfg(12, 4, 32, batch=8, lr=0.1)))


# -------------------------------------------------- Decentralized scan ---

@pytest.mark.slow  # >5.8 s drill; tier-1 re-fit to the 870 s budget on the 2-core box (r20 audit)
def test_decentralized_on_device_scan_bit_equal():
    """The gossip state (nets, push weights) scans n rounds in one
    donated dispatch, bit-equal to the host loop."""
    from fedml_tpu.algos.config import FedConfig as FC
    from fedml_tpu.algos.decentralized import DecentralizedAPI
    from fedml_tpu.core.topology import SymmetricTopologyManager
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(1)
    x = rng.randn(96, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(96, 6), 8)
    cfg = FC(client_num_in_total=6, client_num_per_round=6, comm_round=4,
             epochs=1, batch_size=8, lr=0.2)
    topo = SymmetricTopologyManager(6, 2)
    topo.generate_topology()

    def mk(mode):
        return DecentralizedAPI(LogisticRegression(num_classes=2), fed,
                                None, cfg, topo, mode=mode)

    for mode in ("dsgd", "pushsum"):
        host = mk(mode)
        hl = [host.train_one_round(r)["train_loss"] for r in range(4)]
        dev = mk(mode)
        dl = dev.train_rounds_on_device(4)
        np.testing.assert_allclose(hl, np.asarray(dl), rtol=1e-6,
                                   atol=1e-6)
        _assert_trees_equal(host.nets, dev.nets)
        # Record-derived refusal: nothing streams in gossip.
        with pytest.raises(NotImplementedError, match="gossip"):
            dev.train_rounds_windowed(4)


# -------------------------------------- record-derived refusals ----------

def test_excluded_algorithms_refuse_with_their_declared_reason():
    """Every excluded algorithm's scan-tier entry points raise the
    REASON its capability record declares — not a hand-rolled guard
    message."""
    from fedml_tpu.algos.fedgkt import FedGKTAPI
    from fedml_tpu.algos.hierarchical import HierarchicalFedAvgAPI
    from fedml_tpu.algos.split_nn import SplitNNAPI
    from fedml_tpu.algos.turboaggregate import TurboAggregateAPI
    from fedml_tpu.algos.vertical_fl import VflAPI

    # Reason text reaches the caller verbatim (class-level — no
    # construction needed for the message contract).
    for cls, token in [(SplitNNAPI, "relay ring"),
                       (VflAPI, "partitions FEATURES"),
                       (FedGKTAPI, "alternates TWO models"),
                       (TurboAggregateAPI, "MPC protocol"),
                       (HierarchicalFedAvgAPI, "no fixed scan shape")]:
        msg = refusal(cls, "train_rounds_windowed")
        assert token in msg, (cls, msg)
        assert "opts out" in msg
        rec = record_for(cls)
        assert rec.protocol is None and not rec.windowed \
            and not rec.fused

    # And the instance entry points raise exactly that message
    # (ExcludedScanTiers for the non-FedAvg-family classes; the
    # FedAvg-family guards for the rest).
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    rng = np.random.RandomState(0)
    x = rng.randn(64, 6).astype(np.float32)
    y = (rng.rand(64) > 0.5).astype(np.int32)
    fed = build_federated_arrays(x, y, partition_homo(64, 4), 16)
    turbo = TurboAggregateAPI(LogisticRegression(num_classes=2), fed,
                              None, _cfg(4, 4, 2))
    for entry in (turbo.train_rounds_windowed,
                  turbo.train_rounds_on_device):
        with pytest.raises(NotImplementedError, match="MPC protocol"):
            entry(2)

    class _GKTShell(FedGKTAPI):  # message contract without the 2-model setup
        def __init__(self):
            pass

    with pytest.raises(NotImplementedError, match="alternates TWO models"):
        _GKTShell().train_rounds_windowed(2)


def test_fedseg_record_rides_for_free():
    """FedSeg turned out to need NO exclusion: its round is the shared
    FedAvg round with a segmentation loss, so its record (derived, not
    declared) says every tier rides — the matrix reflects that instead
    of a stale hand-maintained ✗."""
    from fedml_tpu.algos.fedseg import FedSegAPI

    rec = record_for(FedSegAPI)
    assert rec.protocol == "round"
    assert rec.fused and rec.windowed and rec.on_device


# ------------------------------------------- generated matrix drift ------

def test_zoo_records_resolve_and_are_consistent():
    recs = zoo_records()
    assert len(recs) >= 20
    for name, cls, rec in recs:
        if rec.protocol is None:
            assert rec.excluded, f"{name} excluded without a reason"
            assert not (rec.fused or rec.windowed or rec.on_device)
        if rec.windowed and rec.protocol == "round":
            assert rec.pure_server_update, name
    # The converted six all ride fused AND windowed.
    converted = {"FedDyn", "FedNova", "Ditto", "FedBN", "FedGAN",
                 "FedNAS", "FedAc", "ServerAvg"}
    by_name = {name: rec for name, _, rec in recs}
    for name in converted:
        assert by_name[name].fused and by_name[name].windowed, name


def test_execution_matrix_matches_records():
    """Drift test: the committed EXECUTION.md table must be exactly the
    one the records generate (regenerate with
    ``python scripts/gen_support_matrix.py --write``)."""
    import os

    doc = os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                       "EXECUTION.md")
    with open(doc) as f:
        text = f.read()
    assert matrix_block() in text, (
        "docs/EXECUTION.md support matrix drifted from the capability "
        "records — run `python scripts/gen_support_matrix.py --write`")
