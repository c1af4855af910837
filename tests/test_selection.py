"""Power-of-Choice client selection: picks the highest-loss candidates,
reduces to uniform sampling when disabled, and improves the worst-served
client faster than uniform sampling."""

import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.core.sampling import sample_clients, sample_clients_weighted
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.models.lr import LogisticRegression


def _noisy_clients(n_clients=8, per=48, d=6, seed=0):
    """Client c's labels are flipped with probability c/10: later clients
    are strictly harder, giving a known loss ordering for the global
    model."""
    rng = np.random.RandomState(seed)
    w = rng.randn(d)
    xs, ys = [], []
    for c in range(n_clients):
        x = rng.randn(per, d).astype(np.float32)
        y = (x @ w > 0).astype(np.int32)
        flip = rng.rand(per) < (c / 10.0)
        ys.append(np.where(flip, 1 - y, y).astype(np.int32))
        xs.append(x)
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    parts = {c: np.arange(c * per, (c + 1) * per) for c in range(n_clients)}
    return build_federated_arrays(x, y, parts, batch_size=16)


def _cfg(selection="random", cpr=3, rounds=10, candidates=0):
    return FedConfig(client_num_in_total=8, client_num_per_round=cpr,
                     comm_round=rounds, epochs=1, batch_size=16, lr=0.3,
                     client_selection=selection,
                     pow_d_candidates=candidates,
                     frequency_of_the_test=1000)


def test_pow_d_picks_highest_loss_candidates():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg("pow_d", cpr=2, candidates=6))
    # Train a bit so per-client losses reflect the noise ordering.
    for r in range(5):
        api.train_one_round(r)
    round_idx = 7
    idx, wmask = api.sample_round(round_idx)
    # pow_d draws candidates proportional to data fraction (Cho et al.).
    candidates = sample_clients_weighted(round_idx, 8, 6, np.asarray(fed.counts))
    chosen = set(int(i) for i, w in zip(idx, wmask) if w)
    assert chosen <= set(int(c) for c in candidates)
    # the chosen two have the highest eval losses among the candidates

    losses = {int(c): float(api.eval_fn(
        api.net, fed.x[c], fed.y[c], fed.mask[c])["loss"])
        for c in candidates}
    top2 = set(sorted(losses, key=losses.get, reverse=True)[:2])
    assert chosen == top2, (chosen, losses)


def test_random_selection_matches_reference_sampling():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg("random", cpr=3))
    idx, _ = api.sample_round(4)
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx)), np.sort(sample_clients(4, 8, 3)))


def test_pow_d_trains_and_guard_scan():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg("pow_d", cpr=3, rounds=8))
    losses = [api.train_one_round(r)["train_loss"] for r in range(8)]
    assert np.isfinite(losses).all()
    with pytest.raises(NotImplementedError):
        api.train_rounds_on_device(2)
    # Construction must succeed; only the sampling call hits the guard —
    # keeping construction outside pytest.raises pins that.
    bad = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg("fedcs", cpr=3))
    with pytest.raises(ValueError, match="client_selection"):
        bad.sample_round(0)


def test_non_fedavg_algorithms_reject_pow_d():
    """Algorithms without loss-biased sampling must refuse the flag
    loudly instead of silently sampling uniformly."""
    from fedml_tpu.algos.decentralized import DecentralizedAPI
    from fedml_tpu.core.topology import SymmetricTopologyManager

    fed = _noisy_clients()
    cfg = _cfg("pow_d", cpr=8)
    cfg.client_num_per_round = 8
    api = DecentralizedAPI(LogisticRegression(num_classes=2), fed, None,
                           cfg, SymmetricTopologyManager(8, neighbor_num=2))
    with pytest.raises(NotImplementedError, match="client_selection"):
        api.sample_round(0)


def test_pow_d_requires_enough_candidates():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _cfg("pow_d", cpr=4, candidates=2))
    with pytest.raises(ValueError):
        api.sample_round(0)


def test_pow_d_cohort_stable_within_round():
    """Ditto samples again after the global update; the memo must return
    the SAME cohort the global round trained (pow_d depends on the net,
    so an uncached recompute would silently pick a different set)."""
    from fedml_tpu.algos.ditto import DittoAPI

    fed = _noisy_clients()
    api = DittoAPI(LogisticRegression(num_classes=2), fed, None,
                   _cfg("pow_d", cpr=2, rounds=4, candidates=6), lam=0.1)
    for r in range(3):
        before = api.sample_round(r)[0].copy()
        api.train_one_round(r)  # samples internally twice (global+personal)
        after = api.sample_round(r)[0]
        np.testing.assert_array_equal(before, after)


def _ocfg(cpr=3, rounds=10, eps=0.34, **kw):
    return FedConfig(client_num_in_total=8, client_num_per_round=cpr,
                     comm_round=rounds, epochs=1, batch_size=16, lr=0.3,
                     client_selection="oort", oort_epsilon=eps,
                     frequency_of_the_test=1000, **kw)


def test_oort_explores_then_exploits_high_loss_clients():
    """Early rounds explore the unseen; once utilities exist, exploit
    slots go to the highest observed-loss clients (noisy clients 6/7 in
    the fixture have the worst losses by construction)."""
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=3, rounds=12))
    participation = np.zeros(8)
    for r in range(12):
        idx, wmask = api.sample_round(r)
        api.train_one_round(r)
        for i, w in zip(idx, wmask):
            if w:
                participation[int(i)] += 1
    # Everyone got explored at least once...
    assert (api._oort_last >= 0).all(), api._oort_last
    # ...and the hard (high-noise) clients dominate exploitation.
    assert participation[6] + participation[7] > participation[0] + \
        participation[1], participation


def test_oort_utilities_update_only_for_participants():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=2, rounds=4))
    api.train_one_round(0)
    idx, wmask = api.sample_round(0)
    active = {int(i) for i, w in zip(idx, wmask) if w}
    for c in range(8):
        assert (api._oort_last[c] == 0) == (c in active)
    # Utilities are loss * sqrt(n): positive for trained clients.
    assert all(api._oort_utility[c] > 0 for c in active)


def test_oort_deterministic_and_padded():
    fed = _noisy_clients()
    a = FedAvgAPI(LogisticRegression(num_classes=2), fed, None, _ocfg())
    b = FedAvgAPI(LogisticRegression(num_classes=2), fed, None, _ocfg())
    for r in range(5):
        ia, wa = a.sample_round(r)
        ib, wb = b.sample_round(r)
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(wa, wb)
        a.train_one_round(r)
        b.train_one_round(r)


def test_oort_rejects_scan_paths():
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None, _ocfg())
    with pytest.raises(NotImplementedError):
        api.train_rounds_on_device(2)


def test_oort_over_streaming_store():
    from fedml_tpu.data.store import FederatedStore

    rng = np.random.RandomState(0)
    x = rng.randn(8 * 48, 6).astype(np.float32)
    y = (x @ rng.randn(6) > 0).astype(np.int32)
    parts = {c: np.arange(c * 48, (c + 1) * 48) for c in range(8)}
    api = FedAvgAPI(LogisticRegression(num_classes=2),
                    FederatedStore(x, y, parts, batch_size=16), None,
                    _ocfg(cpr=3, rounds=6))
    for r in range(6):
        assert np.isfinite(api.train_one_round(r)["train_loss"])
    assert (api._oort_last >= 0).sum() >= 3


def test_oort_state_checkpoints_and_resumes(tmp_path):
    """Resume must restore utilities/last-seen — otherwise a resumed run
    silently resets to pure exploration (the save_run docstring's exact
    bug class)."""
    from fedml_tpu.obs import CheckpointManager, restore_run, save_run

    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=3, rounds=6))
    for r in range(3):
        api.train_one_round(r)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    save_run(mgr, api, 2)

    fresh = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                      _ocfg(cpr=3, rounds=6))
    assert (fresh._oort_last == -1).all()
    nxt = restore_run(mgr, fresh)
    mgr.close()
    assert nxt == 3
    np.testing.assert_array_equal(fresh._oort_last, api._oort_last)
    np.testing.assert_allclose(fresh._oort_utility, api._oort_utility)


def test_oort_rejects_custom_round_subclasses():
    from fedml_tpu.algos.scaffold import ScaffoldAPI

    fed = _noisy_clients()
    with pytest.raises(NotImplementedError, match="oort"):
        ScaffoldAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=8))


def test_oort_utilities_come_from_in_round_training_losses():
    """Lai et al. §5 semantics (r2 VERDICT stretch #10): the utility
    observable is the client's LOCAL TRAINING loss, captured from the
    jitted round's own outputs — no post-round eval pass. Verified by
    cross-checking the recorded utility against an independent run of
    the same round_fn."""
    import jax

    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=3, rounds=2))
    # Reproduce round 0's exact rng chain to recover its client losses.
    rng0 = api.rng
    _, rnd_rng = jax.random.split(rng0)
    idx, wmask = api.sample_round(0)
    from fedml_tpu.data.batching import gather_clients

    sub = gather_clients(api.train_fed, np.asarray(idx))
    w = sub.counts.astype(np.float32) * np.asarray(wmask)
    out = api.round_fn(api.net, sub.x, sub.y, sub.mask, w, w, rnd_rng)
    assert len(out) == 3  # oort rounds expose per-client losses
    expect = np.asarray(out[2], np.float64)

    api.train_one_round(0)
    counts = np.asarray(fed.counts)[np.asarray(idx)]
    active = np.asarray(wmask) > 0
    got = api._oort_utility[np.asarray(idx)[active]]
    want = expect[active] * np.sqrt(np.maximum(counts[active], 1))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_oort_exploration_sustained_after_full_coverage():
    """Once every client has been seen, the epsilon slice keeps drawing
    uniformly from seen-but-not-exploited clients (Oort's sustained
    epsilon-greedy) instead of silently dropping to zero."""
    fed = _noisy_clients()
    api = FedAvgAPI(LogisticRegression(num_classes=2), fed, None,
                    _ocfg(cpr=4, rounds=30, eps=0.5))
    for r in range(6):
        api.train_one_round(r)
    assert (api._oort_last >= 0).all()  # everyone seen
    # From full coverage on, cohorts must NOT be a deterministic top-k:
    # the epsilon slice (2 of 4 at eps=0.5) varies with the round index.
    cohorts = []
    for r in range(6, 16):
        idx, wmask = api._sample_round_uncached(r)
        cohorts.append(frozenset(
            np.asarray(idx)[np.asarray(wmask) > 0].tolist()))
        api.train_one_round(r)
    assert len(set(cohorts)) > 3, cohorts
