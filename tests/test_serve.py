"""Multi-tenant adapter serving plane (PR 18): the batched forward's
bitwise contracts (B=1 slice == the per-request path, rank-0 rows ==
the dense model, padding inert — all pinned across JITTED paths: jit
fuses differently from eager, so eager-vs-jit comparisons would pin the
wrong thing), the KV-cached decoder against the full flax forward, the
PersonalAdapterStore's concurrent read/write discipline, the
micro-batcher's admission/shed/refuse counters and spans, the JSON
socket front end, and the versioned rollout loop (epoch fence, shadow
gate blocking a poisoned candidate, bit-equal rollback, mid-promotion
restart resume) — including the drill where the training fleet runs
under ChaosTransport."""

import json
import socket
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.comm.codec import tree_to_vector_np
from fedml_tpu.models.adapter import (PersonalAdapterStore,
                                      adapter_model_fns)
from fedml_tpu.models.registry import create_model
from fedml_tpu.models.transformer import lora_delta, lora_delta_batched
from fedml_tpu.obs import trace as obs_trace
from fedml_tpu.serve import (AdapterDecoder, RolloutCoordinator,
                             ServeForward, ServeManager, ServeOverload,
                             ServeRefused, ServeSocketServer,
                             StaleEpochError)

V, T = 61, 10


def _model(rank=2, scope="all"):
    return create_model("transformer_lm", vocab_size=V, d_model=32,
                        n_heads=2, n_layers=2, max_len=64,
                        adapter_rank=rank, adapter_scope=scope)


def _randomized(adapters, seed=7, scale=0.05):
    leaves, treedef = jax.tree.flatten(adapters)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, l.shape, l.dtype) * scale
        for k, l in zip(keys, leaves)])


@pytest.fixture(scope="module")
def stack():
    """One compiled serve stack shared by the module (jit dominates)."""
    model = _model()
    fns = adapter_model_fns(model)
    net = fns.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    glob = _randomized(net.params)
    return {
        "model": model,
        "fns": fns,
        "glob": glob,
        "fwd": ServeForward(fns, glob),
        "dec": AdapterDecoder(model, fns, glob),
    }


def _vecs(stack, b, seed=5):
    """[b, D] personalized rows: row 0 is the global, rows 1.. perturbed."""
    vecs = np.stack([tree_to_vector_np(stack["glob"])] * b)
    rng = np.random.default_rng(seed)
    vecs[1:] += rng.normal(0, 0.03, vecs[1:].shape).astype(np.float32)
    return vecs


def _toks(b, t=T, seed=3):
    return np.array(jax.random.randint(jax.random.PRNGKey(seed), (b, t),
                                       0, V), np.int32)


# -- batched forward bitwise contracts ---------------------------------


def test_lora_delta_batched_b1_slice_bitwise():
    """The batched-B einsum at B=1 is bitwise the per-request matmul
    chain — both jitted (the only paths the plane ever runs)."""
    key = jax.random.PRNGKey(1)
    ka, kb, kx = jax.random.split(key, 3)
    a = jax.random.normal(ka, (16, 4))
    b = jax.random.normal(kb, (4, 8))
    x = jax.random.normal(kx, (5, 16))
    one = jax.jit(partial(lora_delta, alpha=16.0, rank=4))(a, b, x)
    bat = jax.jit(partial(lora_delta_batched, alpha=16.0, rank=4))(
        a[None], b[None], x[None])
    assert np.array_equal(np.asarray(one), np.asarray(bat[0]))


def test_batched_b1_bitwise_equals_sequential(stack):
    """jit(vmap(row)) at B=1 == jit(row): a request served through the
    multi-tenant batch is byte-for-byte the request served alone."""
    vecs, toks = _vecs(stack, 1), _toks(1)
    batched = stack["fwd"].prefill(vecs, toks)
    seq = stack["fwd"].prefill_sequential(vecs, toks)
    assert np.array_equal(np.asarray(batched), np.asarray(seq))


def test_batched_b8_matches_per_row(stack):
    """Eight DIFFERENT personalized models through one dispatch match
    eight per-request dispatches row for row. NOT bitwise at B>1: XLA
    tiles the shared-base matmuls differently for a [8, T, d] operand
    than for eight [1, T, d] dispatches (last-ulp reassociation) — the
    bitwise pin is the B=1 slice above; here the contract is tight
    numerical agreement."""
    vecs, toks = _vecs(stack, 8), _toks(8)
    batched = np.asarray(stack["fwd"].prefill(vecs, toks))
    seq = np.asarray(stack["fwd"].prefill_sequential(vecs, toks))
    np.testing.assert_allclose(batched, seq, atol=1e-5, rtol=1e-5)


def test_rank0_rows_equal_dense_model(stack):
    """A zero adapter vector through the serve forward reproduces the
    DENSE transformer (same frozen base, no injection) run through the
    same batched harness: the adapter machinery adds exactly nothing for
    never-personalized rows. (Same-shape programs — a vmapped dense
    forward — because XLA tiling is batch-shape-dependent; the B=1 pin
    above covers the per-request path.)

    To the last ulp, not byte-identical, since jaxlib 0.9.0 (triaged in
    PR 21): XLA:CPU's fusion emitters (``xla_cpu_use_fusion_emitters``,
    now on by default) fuse the dense projections with the exact-zero
    LoRA terms' adds into other loops than the dense model's, and the
    float32 sums come out reassociated. With ``XLA_FLAGS=
    --xla_cpu_use_fusion_emitters=false`` the two programs are
    byte-identical again."""
    from fedml_tpu.trainer.local import NetState, model_fns

    toks = _toks(2)
    zero = np.zeros((2, stack["fwd"].dim), np.float32)
    served = np.asarray(stack["fwd"].prefill(zero, toks))
    # Dense model: the injected model's param tree minus lora_* leaves IS
    # the dense tree (injection leaves base paths unchanged).
    dense_fns = model_fns(_model(rank=0))
    base = stack["fns"].holder["base"]

    def dense_row(tok):
        logits, _ = dense_fns.apply(NetState(base, {}), tok[None],
                                    train=False)
        return logits[0]

    dense = np.asarray(jax.jit(jax.vmap(dense_row))(jnp.asarray(toks)))
    np.testing.assert_allclose(served, dense, rtol=2e-6, atol=2e-6)


def test_padding_is_inert(stack):
    """Right-padded token tail and zero-padded batch rows change nothing
    for the real prefix/rows (causal attention + vmap row independence)
    — what lets the plane pad every micro-batch to ONE compiled shape.

    Batch-row padding is byte-exact. Sequence padding is exact to the
    last ulp only, since jaxlib 0.9.0 (triaged in PR 21): XLA:CPU now
    lowers these dots through YNNPACK fusions
    (``xla_cpu_experimental_ynn_fusion_type``), whose kernels pick their
    accumulation order from the operand shape, so a row of a ``[2*10,
    d]`` matmul is no longer the bits of the same row in a ``[2*6, d]``
    one. With ``XLA_FLAGS=--xla_cpu_experimental_ynn_fusion_type=`` the
    padded prefix is byte-identical again."""
    vecs, toks = _vecs(stack, 2), _toks(2, t=6)
    full = stack["fwd"].prefill(vecs, toks)
    padded_toks = np.zeros((2, T), np.int32)
    padded_toks[:, :6] = toks
    padded = stack["fwd"].prefill(vecs, padded_toks)
    np.testing.assert_allclose(np.asarray(full), np.asarray(padded[:, :6]),
                               rtol=2e-6, atol=2e-6)
    # batch zero-pad: rows beyond the real traffic don't touch row 0/1
    wide_vecs = np.zeros((4, stack["fwd"].dim), np.float32)
    wide_vecs[:2] = vecs
    wide_toks = np.zeros((4, T), np.int32)
    wide_toks[:2] = padded_toks
    wide = stack["fwd"].prefill(wide_vecs, wide_toks)
    assert np.array_equal(padded, wide[:2])


def test_decoder_matches_full_forward(stack):
    """KV-cached prefill+decode tracks the full flax forward: last-token
    logits allclose, greedy continuations token-identical."""
    fwd, dec = stack["fwd"], stack["dec"]
    vecs, toks = _vecs(stack, 4), _toks(4)
    stacked = fwd.stacked_tree(vecs)
    full = np.asarray(fwd.batched(stacked, jnp.asarray(toks)))
    last, _ = dec.prefill(stacked, toks)
    np.testing.assert_allclose(np.asarray(last), full[:, -1], atol=2e-5)
    n_new = 4
    gen = np.asarray(dec.generate(stacked, toks, n_new))
    cur = toks.copy()
    for step in range(n_new):
        logits = np.asarray(fwd.batched(stacked, jnp.asarray(cur)))
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        assert np.array_equal(gen[:, step], nxt)
        cur = np.concatenate([cur, nxt[:, None]], 1)


def test_decoder_short_prompt_decodes_from_true_last_token(stack):
    """A right-padded short prompt decodes from its TRUE last token, not
    the pad tail: per-row ``lens`` gathers the lens-1 logits for the
    first step and rewinds the cache's per-row write offsets, so every
    generated token matches the greedy continuation of the UNPADDED
    prompt through the full forward — the 'padding is inert' contract
    on the decode path."""
    fwd, dec = stack["fwd"], stack["dec"]
    true_len, n_new = 5, 3
    vecs, toks = _vecs(stack, 2), _toks(2, t=true_len)
    padded = np.zeros((2, T), np.int32)
    padded[:, :true_len] = toks
    stacked = fwd.stacked_tree(vecs)
    lens = np.full(2, true_len, np.int32)
    last, _ = dec.prefill(stacked, padded, lens=lens)
    full = np.asarray(fwd.batched(stacked, jnp.asarray(toks)))
    np.testing.assert_allclose(np.asarray(last), full[:, -1], atol=2e-5)
    gen = np.asarray(dec.generate(stacked, padded, n_new, lens=lens))
    assert np.array_equal(gen[:, 0], full[:, -1].argmax(-1))
    cur = toks.copy()
    for step in range(n_new):
        logits = np.asarray(fwd.batched(stacked, jnp.asarray(cur)))
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        assert np.array_equal(gen[:, step], nxt)
        cur = np.concatenate([cur, nxt[:, None]], 1)


def test_decoder_mixed_lengths_decode_independently(stack):
    """Rows of DIFFERENT true lengths in one padded batch each continue
    from their own last token (per-row cache positions), matching the
    row served alone at its true length."""
    fwd, dec = stack["fwd"], stack["dec"]
    vecs = _vecs(stack, 2)
    lens = np.array([3, 7], np.int32)
    padded = _toks(2, t=T)
    for i, ln in enumerate(lens):
        padded[i, ln:] = 0
    stacked = fwd.stacked_tree(vecs)
    gen = np.asarray(dec.generate(stacked, padded, 2, lens=lens))
    for i, ln in enumerate(lens):
        solo = np.asarray(dec.generate(
            fwd.stacked_tree(vecs[i:i + 1]), padded[i:i + 1, :ln], 2))
        assert np.array_equal(gen[i], solo[0])


def test_pick_attention_crossover():
    from fedml_tpu.serve import FLASH_CROSSOVER_T, pick_attention

    assert pick_attention(FLASH_CROSSOVER_T - 1) == "dense"
    assert pick_attention(FLASH_CROSSOVER_T) == "flash"


# -- store concurrency --------------------------------------------------


def test_store_concurrent_gather_never_tears(stack):
    """A serving-plane gather racing training-fleet scatters must never
    observe a torn row: the writer only ever writes CONSTANT rows, so
    any gathered row with unequal elements is a caught half-write."""
    store = PersonalAdapterStore(8, stack["glob"])
    dim = store.dim
    stop = threading.Event()
    fail = []

    def writer():
        c = 0.0
        while not stop.is_set():
            c += 1.0
            store.scatter(np.arange(8),
                          np.full((8, dim), c, np.float32))

    def reader():
        for _ in range(300):
            rows = store.gather(np.arange(8), stack["glob"])
            spread = rows.max(axis=1) - rows.min(axis=1)
            if (spread != 0).any():
                fail.append(rows)
                return

    # A row never scattered reads as the default adapters, which are not
    # a constant row: the reader must not get ahead of the first scatter.
    store.scatter(np.arange(8), np.zeros((8, dim), np.float32))
    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start(); r.start()
    r.join(timeout=60)
    stop.set()
    w.join(timeout=60)
    assert not r.is_alive() and not w.is_alive()
    assert not fail, "gather returned a torn row"


# -- request plane ------------------------------------------------------


def _manager(stack, **kw):
    kw.setdefault("seq_len", T)
    kw.setdefault("max_batch", 4)
    return ServeManager(stack["fwd"], kw.pop("store", None), stack["glob"],
                        **kw)


def test_serve_batch_results_counters_and_spans(stack):
    """One synchronous micro-batch: every request resolves to its own
    unpadded logits slice, counters move, gather/prefill spans emit."""
    store = PersonalAdapterStore(8, stack["glob"])
    pvec = tree_to_vector_np(stack["glob"]) + 0.05
    store.scatter([2], pvec[None])
    mgr = _manager(stack, store=store)
    tracer = obs_trace.SpanTracer()
    with obs_trace.using(tracer):
        reqs = [mgr.submit(i, _toks(1, t=4 + i)[0]) for i in range(3)]
        mgr.serve_batch([mgr._q.get_nowait() for _ in range(3)])
    for i, req in enumerate(reqs):
        logits, gen = req.result(5)
        assert logits.shape == (4 + i, V)
        assert gen is None
    # client 2's personalized row actually served (differs from global)
    glob_logits, _ = reqs[0].result(5)
    assert not np.array_equal(reqs[2].result(5)[0][:4], glob_logits)
    stats = mgr.stats()
    assert stats["serve/admitted"] == 3 and stats["serve/served"] == 3
    assert stats["serve/batch_fill_count"] == 1
    names = {e["name"] for e in tracer._events}
    assert {"serve.gather", "serve.prefill"} <= names


def test_submit_sheds_on_full_queue_and_refuses_malformed(stack):
    mgr = _manager(stack, queue_cap=2)
    mgr.submit(0, [1, 2])
    mgr.submit(1, [3])
    with pytest.raises(ServeOverload):
        mgr.submit(2, [4])
    with pytest.raises(ServeRefused):
        mgr.submit(0, list(range(T + 5)))  # longer than the plane's seq
    with pytest.raises(ServeRefused):
        mgr.submit(0, [])
    stats = mgr.stats()
    assert stats["serve/shed"] == 1 and stats["serve/refused"] == 2


def test_micro_batcher_thread_serves_and_decodes(stack):
    """The deadline-or-batch-full loop end to end, decode included."""
    with _manager(stack, decoder=stack["dec"], deadline_s=0.005) as mgr:
        reqs = [mgr.submit(i, [1, 2, 3], max_new_tokens=2)
                for i in range(6)]
        for req in reqs:
            logits, gen = req.result(60)
            assert logits.shape == (3, V) and gen.shape == (2,)
    stats = mgr.stats()
    # zero-count metrics are omitted from registry snapshots
    assert stats["serve/served"] == 6 and stats.get("serve/shed", 0) == 0
    assert stats["serve/latency_ms_count"] == 6


def test_serve_batch_decode_consistent_with_next_token(stack):
    """Short prompts through the padded plane: each request's first
    generated token is the argmax of its OWN true-last-position logits —
    the same value the socket reply computes — never a continuation of
    the pad tail."""
    mgr = _manager(stack, decoder=stack["dec"])
    reqs = [mgr.submit(i, [1, 2, 3][:ln], max_new_tokens=2)
            for i, ln in enumerate((3, 1))]
    mgr.serve_batch([mgr._q.get_nowait() for _ in range(2)])
    for req in reqs:
        logits, gen = req.result(5)
        assert gen.shape == (2,)
        assert gen[0] == int(np.argmax(logits[-1]))


def test_submit_refuses_bad_max_new_tokens(stack):
    """Decode budget is validated at admission: negative counts and
    requests whose seq_len + max_new_tokens exceed the decoder's
    max_len (where JAX OOB clamping would serve garbage) refuse loudly."""
    mgr = _manager(stack, decoder=stack["dec"])
    with pytest.raises(ServeRefused, match="max_new_tokens"):
        mgr.submit(0, [1, 2], max_new_tokens=-1)
    over = stack["dec"].max_len - mgr.seq_len + 1
    with pytest.raises(ServeRefused, match="decoder budget"):
        mgr.submit(0, [1, 2], max_new_tokens=over)
    # the largest in-budget count admits (bench runs exactly at it)
    mgr.submit(0, [1, 2], max_new_tokens=over - 1)
    stats = mgr.stats()
    assert stats["serve/refused"] == 2 and stats["serve/admitted"] == 1


def test_close_drains_queued_requests(stack):
    """Shutdown never wedges a waiter: requests still queued when the
    batcher exits are completed with a refusal, and post-close submits
    refuse instead of queueing into the void."""
    from fedml_tpu.serve.plane import ServeRequest

    mgr = _manager(stack)
    mgr.start()
    mgr.close()
    # a request that slipped into the queue concurrently with shutdown
    straggler = ServeRequest(0, np.array([1, 2], np.int32), 0, 0.0)
    mgr._q.put_nowait(straggler)
    mgr.close()  # idempotent close drains it
    with pytest.raises(ServeRefused, match="shut down"):
        straggler.result(5)
    with pytest.raises(ServeRefused, match="shut down"):
        mgr.submit(0, [1, 2])


def test_shadow_mirror_compiles_one_batch_shape(stack):
    """The mirror CE runs on the already-padded [max_batch, seq_len]
    tokens: serving batches of DIFFERENT occupancy while a candidate is
    staged reuses one compiled program — no fresh XLA compile stalls the
    serving thread mid-traffic."""
    mgr = _manager(stack)
    mgr.set_shadow(1, stack["glob"])
    shapes = []
    real_ce = mgr._ce

    def spy(stacked, toks, m):
        shapes.append(tuple(toks.shape))
        return real_ce(stacked, toks, m)

    mgr._ce = spy
    for n in (1, 3, 2):
        reqs = [mgr.submit(i, [1, 2, 3, 4]) for i in range(n)]
        mgr.serve_batch([mgr._q.get_nowait() for _ in range(n)])
        for r in reqs:
            r.result(5)
    assert set(shapes) == {(mgr.max_batch, mgr.seq_len)}
    assert mgr.shadow_scores()["tokens"] == 6 * 3  # pad rows masked out


def test_socket_front_end_roundtrip(stack):
    with _manager(stack, decoder=stack["dec"]) as mgr:
        with ServeSocketServer(mgr, 0) as srv:
            conn = socket.create_connection(("127.0.0.1", srv.port),
                                            timeout=30)
            conn.sendall((json.dumps({"client": 0, "tokens": [1, 2, 3],
                                      "max_new_tokens": 2}) + "\n")
                         .encode())
            buf = b""
            while b"\n" not in buf:
                buf += conn.recv(4096)
            conn.close()
    reply = json.loads(buf.split(b"\n")[0])
    assert len(reply["generated"]) == 2
    # the socket's next_token is the argmax the in-process path computes
    logits = stack["fwd"].prefill(
        tree_to_vector_np(stack["glob"])[None],
        np.array([[1, 2, 3] + [0] * (T - 3)], np.int32))
    assert reply["next_token"] == int(logits[0, 2].argmax())


# -- rollout loop -------------------------------------------------------


def _drive_shadow(mgr, n=4):
    """Mirrored traffic through an UNSTARTED manager: submit + serve the
    micro-batch synchronously (deterministic — no batcher thread)."""
    for _ in range(n):
        req = mgr.submit(0, [1, 2, 3, 4, 5])
        mgr.serve_batch([mgr._q.get_nowait()])
        req.result(5)


def test_rollout_gate_promotes_blocks_poison_rolls_back(stack, tmp_path):
    """The full drill: a clean candidate promotes through the shadow
    gate, a NaN-poisoned one is blocked and never becomes live, and
    rollback restores the displaced version BIT-EQUAL."""
    mgr = _manager(stack)
    co = RolloutCoordinator(mgr, directory=str(tmp_path),
                            min_shadow_tokens=8)
    v1 = co.publish(stack["glob"], epoch=1)
    with pytest.raises(StaleEpochError):
        co.publish(stack["glob"], epoch=1)  # zombie incarnation fenced
    # not enough mirrored evidence yet -> stays staged
    assert co.try_promote()["promoted"] is False
    _drive_shadow(mgr)
    verdict = co.try_promote()
    assert verdict["promoted"] and mgr.live_version == v1
    promoted_vec = mgr._vec(mgr.live_adapters()).copy()
    # poisoned candidate: NaN weights must never go live
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), stack["glob"])
    co.publish(bad, epoch=2)
    _drive_shadow(mgr)
    verdict = co.try_promote()
    assert not verdict["promoted"]
    assert verdict["reason"] == "candidate_ce_not_finite"
    assert mgr.live_version == v1  # live untouched by the blocked cand
    co.discard()
    # one-step rollback: bit-equal to the pre-promotion live
    rolled = co.rollback()
    assert rolled == 0
    assert np.array_equal(mgr._vec(mgr.live_adapters()),
                          tree_to_vector_np(stack["glob"]))
    # ...and reversible: rolling back again restores the promoted vec
    co.rollback()
    assert np.array_equal(mgr._vec(mgr.live_adapters()), promoted_vec)
    co.close()
    mgr.close()


def test_rollout_regression_gate_blocks_worse_candidate(stack):
    """A FINITE but regressing candidate (CE above the live arm's by
    more than the tolerance on the mirrored traffic) is blocked by the
    relative-tolerance gate. Arms chosen by measured CE on this traffic:
    large-noise adapters land near the uniform distribution (~log V)
    while the module's mild-noise globals sit visibly above it."""
    # Arms re-picked in PR 21 by measured CE on this traffic: JAX 0.5
    # made jax_threefry_partitionable the default, which changed every
    # seeded draw (model init and these adapters). live 3.25 vs the
    # module global's 4.38 (was 4.14 vs 4.65 under the old stream).
    live = _randomized(stack["glob"], seed=13, scale=5.0)  # lower CE
    mgr = ServeManager(stack["fwd"], None, live, seq_len=T, max_batch=4)
    co = RolloutCoordinator(mgr, min_shadow_tokens=8, regression_tol=0.02)
    co.publish(stack["glob"], epoch=1)  # higher-CE candidate
    _drive_shadow(mgr)
    verdict = co.try_promote()
    assert not verdict["promoted"]
    assert verdict["reason"].startswith("regression")
    assert verdict["cand_ce"] > verdict["live_ce"]
    mgr.close()


def test_rollout_restart_resumes_mid_promotion(stack, tmp_path):
    """Coordinator dies between publish and promote: the next
    incarnation restores the fenced epoch, re-stages the candidate
    shadow, and the promotion completes — on a fake clock, so the drill
    is deterministic."""
    from fedml_tpu.sim.clock import VirtualClock

    # Candidate re-picked in PR 21 (the partitionable threefry stream,
    # see the regression-gate test): CE 4.27 on this traffic against the
    # live global's 4.38, inside the 2 % gate; the old seed now lands at
    # 4.51 and is — correctly — blocked.
    cand = _randomized(stack["glob"], seed=2, scale=0.04)
    mgr = ServeManager(stack["fwd"], None, stack["glob"], seq_len=T,
                       max_batch=4, clock=VirtualClock())
    co = RolloutCoordinator(mgr, directory=str(tmp_path),
                            min_shadow_tokens=8)
    v = co.publish(cand, epoch=3)
    co.close()  # crash before any shadow traffic
    mgr2 = ServeManager(stack["fwd"], None, stack["glob"], seq_len=T,
                        max_batch=4, clock=VirtualClock())
    co2 = RolloutCoordinator(mgr2, directory=str(tmp_path),
                             min_shadow_tokens=8)
    assert co2.fence_epoch == 3 and co2.cand_version == v
    assert mgr2.shadow_scores()["candidate_version"] == v
    with pytest.raises(StaleEpochError):
        co2.publish(cand, epoch=3)  # the dead incarnation's epoch
    _drive_shadow(mgr2)
    verdict = co2.try_promote()
    assert verdict["promoted"] and co2.live_version == v
    assert np.array_equal(mgr2._vec(mgr2.live_adapters()),
                          tree_to_vector_np(cand))
    # third incarnation restores the PROMOTED state
    co2.close()
    mgr3 = ServeManager(stack["fwd"], None, stack["glob"], seq_len=T,
                        max_batch=4, clock=VirtualClock())
    co3 = RolloutCoordinator(mgr3, directory=str(tmp_path))
    assert co3.live_version == v and co3.cand_version is None
    assert np.array_equal(mgr3._vec(mgr3.live_adapters()),
                          tree_to_vector_np(cand))
    co3.close()


@pytest.mark.slow  # FedBuff federation under chaos + serve-stack jit
def test_fedbuff_chaos_publishes_through_rollout_gate(stack):
    """The training-fleet drill: a FedBuff federation running under
    ChaosTransport (duplication/delay/reorder — drops need the sync
    tier's round-timeout machinery to stay live; FedBuff's async
    protocol has no per-message retry) produces the v1 snapshot; it
    promotes through the shadow gate, a poisoned v2 is blocked, and
    rollback restores the chaos-trained global bit-equal."""
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed
    from fedml_tpu.comm.resilience import ChaosSpec
    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo
    from fedml_tpu.trainer.local import seq_softmax_ce

    rng = np.random.RandomState(0)
    seqs = rng.randint(1, V, size=(32, T + 1))
    fed = build_federated_arrays(seqs[:, :T].astype(np.int32),
                                 seqs[:, 1:].astype(np.int32),
                                 partition_homo(32, 4), 4)
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=2,
                    comm_round=2, epochs=1, batch_size=4, lr=0.1, seed=0,
                    adapter_rank=2)
    srv = FedML_FedBuff_distributed(
        _model(rank=2, scope="attn"), fed, None, cfg, buffer_k=2,
        loss_fn=partial(seq_softmax_ce, pad_id=0),
        chaos=ChaosSpec(seed=3, dup_p=0.3, delay_p=0.3, max_delay_s=0.02))
    trained = jax.tree.map(np.asarray, srv.net.params)

    sfns = adapter_model_fns(_model(rank=2, scope="attn"),
                             holder=srv.adapter_holder)
    fwd = ServeForward(sfns, trained)
    mgr = ServeManager(fwd, None, jax.tree.map(np.zeros_like, trained),
                       seq_len=T, max_batch=4)
    co = RolloutCoordinator(mgr, min_shadow_tokens=8, regression_tol=10.0)
    v1 = co.publish(trained, epoch=srv.epoch if hasattr(srv, "epoch")
                    else 1)
    _drive_shadow(mgr)
    assert co.try_promote()["promoted"]
    assert np.array_equal(mgr._vec(mgr.live_adapters()),
                          tree_to_vector_np(trained))
    poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), trained)
    co.publish(poisoned, epoch=99)
    _drive_shadow(mgr)
    assert not co.try_promote()["promoted"]
    co.discard()
    co.rollback()
    co.rollback()  # back to the chaos-trained global, bit-equal
    assert np.array_equal(mgr._vec(mgr.live_adapters()),
                          tree_to_vector_np(trained))
    mgr.close()


# -- driver refusal matrix ----------------------------------------------


def test_reject_serve_flags_matrix():
    """Every serve knob refuses on every non-serving driver; defaults
    pass silently (the PR 4/14 convention)."""
    from fedml_tpu.exp.args import parse_args, reject_serve_flags

    for flags in (["--serve"], ["--serve_port", "7070"],
                  ["--serve_max_batch", "8"],
                  ["--serve_deadline_ms", "1.0"],
                  ["--serve_requests", "5"]):
        args = parse_args(flags)
        for driver in ("the cross-silo pipeline",
                       "the centralized baseline", "FedGAN", "FedAvg"):
            with pytest.raises(SystemExit, match="serv"):
                reject_serve_flags(args, driver)
    reject_serve_flags(parse_args([]), "FedAvg")


def test_drivers_refuse_serve_flags():
    from fedml_tpu.exp import main_extra
    from fedml_tpu.exp.args import parse_args
    from fedml_tpu.exp.run import run

    # simulator tiers never serve
    with pytest.raises(SystemExit, match="serving plane"):
        run(parse_args(["--serve"]), "FedAvg")
    # specialty loops refuse
    with pytest.raises(SystemExit, match="serving plane"):
        main_extra.main(["--algorithm", "FedGAN", "--serve"])
    # FedBuff without --serve refuses the dependent knobs
    with pytest.raises(SystemExit, match="serve_requests"):
        main_extra.main(["--algorithm", "FedBuff",
                         "--serve_requests", "4"])
    # FedBuff with --serve but no adapters refuses
    with pytest.raises(SystemExit, match="adapter_rank"):
        main_extra.main(["--algorithm", "FedBuff", "--serve"])


def test_centralized_and_cross_silo_refuse_serve_flags():
    from fedml_tpu.exp.args import parse_args
    from fedml_tpu.exp.main_centralized import run_centralized
    from fedml_tpu.exp.main_cross_silo import main as cs_main

    with pytest.raises(SystemExit, match="serving plane"):
        run_centralized(parse_args(["--serve"]))
    with pytest.raises(SystemExit, match="serving plane"):
        cs_main(["--rank", "0", "--size", "2", "--serve"])
