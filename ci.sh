#!/bin/bash
# CI smoke script — parity with the reference's CI-script-*.sh family
# (pyflakes gate + tiny-config end-to-end runs, CI-script-fedavg.sh:6-56).
# The pytest suite (python -m pytest tests/ -x -q) is the primary gate; this
# script is the fast end-to-end sanity layer.
#
# Suite cost structure (r6 re-audit on the 2-core box, where the tier-1
# verify runs under a hard `timeout 870`; r5 numbers were from a 1-core
# box):
#   fast lane   python -m pytest tests/ -m "not slow" -x -q   ~12 min
#               (must FIT the 870 s tier-1 budget with margin: every
#               test >20 s on the 2-core box was slow-marked in r6 —
#               --durations=40 audit — including the 342k-client store
#               instantiation, remat/bf16/fedgkt/fednas exact-match
#               runs, and the fedseg/fedgan/sequence CLI e2e tests)
#   slow lane   python -m pytest tests/ -m slow -q            ~2.5-3 h
#               (FEMNIST-CNN 3400c/60r convergence ~70 min is the long
#               pole; plus everything moved down in the r6 audit)
#   this script                                               ~10 min
# The fast lane keeps full algorithmic coverage (every algorithm still
# trains 2-4 tiny rounds there) and the windowed/streaming bit-equality
# pins; reference-scale loops and >20 s exact-match runs live slow.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

echo "== static check (compileall + fedlint; the reference ran pyflakes) =="
python -m compileall -q fedml_tpu
# fedlint: the repo's own AST analyzer, both rule families — the JAX
# pitfalls PR 1 shipped (carried rng chains, staging aliasing, host
# syncs in hot paths, recompile hazards, donation misuse) and the
# protocol/concurrency family (P1 thread-shared state, P2 drop-without-
# reply, P3 flag-refusal coverage, P4 copy-divergence — docs/LINT.md).
# Exits nonzero on any finding not covered by fedlint.baseline.json
# (kept empty: clean); U1 dead suppressions gate here too (strict).
# The JSON finding list lands beside the smoke logs as a CI artifact.
lint_dir="${CI_RUN_DIR:-$(mktemp -d "${TMPDIR:-/tmp}/fedlint-ci.XXXXXX")}"
mkdir -p "$lint_dir"
lint_t0=$SECONDS
python scripts/fedlint.py fedml_tpu --no-unused-suppressions \
    --format=json > "$lint_dir/fedlint.json" \
    || { cat "$lint_dir/fedlint.json"; exit 1; }
echo "fedlint: clean in $((SECONDS - lint_t0))s" \
     "(artifact: $lint_dir/fedlint.json)"

common="--client_num_in_total 4 --client_num_per_round 4 --batch_size 8 \
        --comm_round 2 --epochs 1 --ci 1"

echo "== standalone FedAvg on LEAF-shaped mnist =="
python -m fedml_tpu.exp.main_fedavg --model lr --dataset mnist $common

echo "== FedOpt (server adam) on synthetic =="
python -m fedml_tpu.exp.run --algorithm FedOpt --server_optimizer adam \
    --model lr --dataset synthetic_1_1 $common

echo "== FedAvg sharded over 4 devices =="
python -m fedml_tpu.exp.main_fedavg --model lr --dataset synthetic_1_1 \
    --num_devices 4 $common

echo "== SCAFFOLD / q-FedAvg / Ditto (drift, fairness, personalization) =="
python -m fedml_tpu.exp.run --algorithm Scaffold \
    --model lr --dataset synthetic_1_1 $common
python -m fedml_tpu.exp.run --algorithm QFedAvg --qffl_q 2.0 \
    --model lr --dataset synthetic_1_1 $common
python -m fedml_tpu.exp.run --algorithm Ditto --ditto_lam 0.1 \
    --model lr --dataset synthetic_1_1 $common

echo "== centralized baseline (mesh data parallelism) =="
python -m fedml_tpu.exp.main_centralized --model lr --dataset synthetic_1_1 \
    --num_devices 8 $common

echo "== reproduce-baselines wiring (synthetic sanity, one config) =="
CI_LITE=1 bash scripts/reproduce_baselines.sh synthetic_lr > /dev/null

echo "== fed_cifar100 ResNet-GN wiring row (CI_LITE_DEPTH compile proxy) =="
# resnet10_gn: same flags/loader as the published resnet18_gn config at a
# CPU-compilable depth (~100 s here) — the row is exercised, not skipped.
CI_LITE=1 CI_LITE_DEPTH=10 bash scripts/reproduce_baselines.sh \
  fed_cifar100_resnet18 > /dev/null

echo "== DP-SGD clients (example-level privacy) =="
python -m fedml_tpu.exp.main_fedavg --model lr --dataset synthetic_1_1 \
    --dp_clip 1.0 --dp_noise_multiplier 0.5 $common

echo "== sharded client directory (million-client tier, small-G smoke) =="
python - <<'PYEOF'
import tempfile, numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.data.directory import ShardedFederatedStore
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.models.lr import LogisticRegression

def builder(s):
    rng = np.random.RandomState(100 + s)
    counts = 1 + rng.randint(0, 6, 16).astype(np.int64)
    tot = int(counts.sum())
    return (rng.randn(tot, 6).astype(np.float32),
            (rng.rand(tot) > 0.5).astype(np.int32), counts)

with tempfile.TemporaryDirectory() as td:
    store = ShardedFederatedStore.from_shard_builder(
        builder, 4, batch_size=8, spill_dir=td)
    assert store.memmapped and store.num_clients == 64
    # flat-store twin over the same generated data: one cohort bit-equal
    xs, ys, cs = zip(*(builder(s) for s in range(4)))
    counts = np.concatenate(cs)
    edges = np.concatenate([[0], np.cumsum(counts)])
    parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(64)}
    flat = FederatedStore(np.concatenate(xs), np.concatenate(ys), parts,
                          batch_size=8)
    idx = np.array([0, 17, 33, 63, 5])
    a, b = flat.gather_cohort(idx), store.gather_cohort(idx)
    for l, r in zip((a.x, a.y, a.mask, a.counts), (b.x, b.y, b.mask, b.counts)):
        np.testing.assert_array_equal(np.asarray(l), np.asarray(r))
    cfg = FedConfig(client_num_in_total=64, client_num_per_round=6,
                    comm_round=2, epochs=1, batch_size=8, lr=0.3)
    api = FedAvgAPI(LogisticRegression(num_classes=2), store, None, cfg)
    for r in range(2):
        assert np.isfinite(api.train_one_round(r)["train_loss"])
    # directory sampling is re-sharding-invariant (G=4 vs flat G=1)
    from fedml_tpu.data.directory import ClientDirectory
    ref = ClientDirectory(store.counts, np.zeros(64, int), 1)
    assert np.array_equal(store.directory.sample_cohort(1, 6),
                          ref.sample_cohort(1, 6))
print("sharded directory smoke OK")
PYEOF

echo "== pod compute plane: host-grouped reduce on a forced 2x4 DCN mesh =="
python - <<'PYEOF'
import numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.parallel.multihost import simulated_dcn_mesh

# 16 learnable clients over a SIMULATED 2x4 DCN x ICI mesh (single
# process, forced factorization): real training, mean bit-equality
# group_reduce=True vs False (the hierarchical partial-sum program is
# the mean path either way), median-of-host-medians in the clean
# ballpark, and the O(G) traffic gauges live.
rng = np.random.RandomState(0)
n, per, d = 16, 32, 6
w_true = rng.randn(d)
x = rng.randn(n * per, d).astype(np.float32)
y = (x @ w_true > 0).astype(np.int32)
parts = {c: np.arange(c * per, (c + 1) * per) for c in range(n)}
fed = build_federated_arrays(x, y, parts, batch_size=16)
test = (x.reshape(-1, 16, d), y.reshape(-1, 16),
        np.ones((n * per // 16, 16), np.float32))
mesh = simulated_dcn_mesh(2, 4)
mk = lambda **kw: FedAvgAPI(
    LogisticRegression(num_classes=2), fed, test,
    FedConfig(client_num_in_total=n, client_num_per_round=8,
              comm_round=6, epochs=1, batch_size=16, lr=0.3,
              frequency_of_the_test=1000, **kw), mesh=mesh)
flat, grp = mk(), mk(group_reduce=True)
for r in range(6):
    flat.train_one_round(r)
    grp.train_one_round(r)
import jax
for a, b in zip(jax.tree.leaves(flat.net.params),
                jax.tree.leaves(grp.net.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
acc = float(np.asarray(grp.evaluate()["accuracy"]))
med = mk(group_reduce=True, aggregator="coord_median")
for r in range(6):
    med.train_one_round(r)
macc = float(np.asarray(med.evaluate()["accuracy"]))
assert acc > 0.8, acc
assert macc > acc - 0.15, (macc, acc)  # median-of-medians clean ballpark
prof = grp.reduce_profile()
assert prof["dcn_partials"] == 2  # G = hosts, not the 8-client cohort
assert prof["dcn_rounds"] == 6
print(f"pod reduce smoke OK: mean bit-equal, acc {acc:.2f}, "
      f"median-of-host-medians {macc:.2f}, DCN partials/round "
      f"{int(prof['dcn_partials'])} (G) vs flat "
      f"{int(prof['dcn_flat_bytes_per_round'] // (prof['dcn_bytes_per_round'] // 2))} (C)")
PYEOF

echo "== fused donated round step + lane-fill compute layout =="
python - <<'PYEOF'
import jax, numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.models.cnn import CNNOriginalFedAvg
from fedml_tpu.obs.sanitizer import donation_audit, sanitized

rng = np.random.RandomState(0)
x = rng.rand(8 * 16, 28, 28, 1).astype(np.float32)
y = rng.randint(0, 10, len(x)).astype(np.int32)
fed = build_federated_arrays(x, y, partition_homo(len(x), 8), 8)
cfg = FedConfig(client_num_in_total=8, client_num_per_round=4,
                comm_round=100, epochs=1, batch_size=8, lr=0.05,
                compute_layout="auto")
# Deliberately misaligned conv widths: the layout policy pads them, and
# the logical shapes must still be what everything above the step sees.
api = FedAvgAPI(CNNOriginalFedAvg(num_classes=10, widths=(12, 20)),
                fed, None, cfg)
assert api._layout is not None and not api._layout.is_identity
assert api._fused_round_step() is not None
logical = [tuple(l.shape) for l in jax.tree.leaves(api.net)]
api.train_one_round(0)  # compile once
old = api.net
with sanitized(transfer="allow") as rep:  # strict: zero recompiles
    with donation_audit(api.net) as audit:
        base = audit.sample()
        for r in range(1, 3):
            m = api.train_one_round(r)
            assert np.isfinite(m["train_loss"])
            audit.sample()
assert all(l.is_deleted() for l in jax.tree.leaves(old))  # donated
assert audit.peak <= base + 0.25, (audit.peak, base)
assert [tuple(l.shape) for l in jax.tree.leaves(api.net)] == logical
print("fused+padded smoke OK: zero recompiles, donated carry, "
      f"logical shapes held ({api._layout.describe()})")
PYEOF

echo "== whole-zoo carry records: FedDyn windowed bit-equal to host loop =="
python - <<'PYEOF'
import jax, numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.feddyn import FedDynAPI
from fedml_tpu.data.store import FederatedStore
from fedml_tpu.models.lr import LogisticRegression

# Power-law counts so the window-max bucket forcing path actually runs.
rng = np.random.RandomState(0)
counts = np.concatenate([[120], rng.randint(10, 40, 7)])
edges = np.concatenate([[0], np.cumsum(counts)])
x = rng.randn(int(counts.sum()), 6).astype(np.float32)
y = (x @ rng.randn(6) > 0).astype(np.int32)
parts = {c: np.arange(edges[c], edges[c + 1]) for c in range(8)}

def mk():
    cfg = FedConfig(client_num_in_total=8, client_num_per_round=3,
                    comm_round=5, epochs=1, batch_size=8, lr=0.1)
    return FedDynAPI(LogisticRegression(num_classes=2),
                     FederatedStore(x, y, parts, batch_size=8), None,
                     cfg, alpha=0.05)

host, win = mk(), mk()
la = [host.train_one_round(r)["train_loss"] for r in range(5)]
lb = win.train_rounds_windowed(5, window=2)  # non-dividing: 2+2+1
np.testing.assert_array_equal(la, lb)
for a, b in zip(jax.tree.leaves((host.net.params, host.server_h,
                                 host.client_grads)),
                jax.tree.leaves((win.net.params, win.server_h,
                                 win.client_grads))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
rec = win.capability()
assert rec.fused and rec.windowed
print("zoo carry-record smoke OK: FedDyn windowed == host "
      f"(5 rounds, W=2, losses[-1]={lb[-1]:.4f})")
PYEOF

echo "== compressed distributed smoke (int8+top-k wire codec over loopback) =="
python - <<'PYEOF'
import numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression

x, y = make_classification(240, n_features=16, n_classes=4, seed=1)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)
cfg = FedConfig(client_num_in_total=4, client_num_per_round=4, comm_round=2,
                epochs=2, batch_size=16, lr=0.3, frequency_of_the_test=1)
agg = FedML_FedAvg_distributed(
    LogisticRegression(num_classes=4), fed, test, cfg,
    wire_codec="topk0.25+int8", loopback_wire="tensor")
accs = [h["accuracy"] for h in agg.test_history]
assert accs and accs[-1] > 0.5, accs       # accuracy sanity, 2 rounds
h = agg.final_health
assert h["bytes_rx"] > 0 and h["bytes_tx"] > 0, h  # bytes counted
print(f"compressed smoke OK: acc={accs[-1]:.2f}, "
      f"rx={h['bytes_rx']}B tx={h['bytes_tx']}B")
PYEOF

echo "== adapter finetune smoke (frozen base + topk0.1+int8 adapter deltas) =="
python - <<'PYEOF'
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.models.adapter import adapter_model_fns
from fedml_tpu.models.registry import create_model
from fedml_tpu.trainer.local import seq_softmax_ce

V, T, B = 64, 16, 4
rng = np.random.RandomState(0)
seqs = rng.randint(1, V, size=(32, T + 1))
fed = build_federated_arrays(seqs[:, :T].astype(np.int32),
                             seqs[:, 1:].astype(np.int32),
                             partition_homo(32, 4), B)
loss = partial(seq_softmax_ce, pad_id=0)


def mk(rank):
    return create_model("transformer_lm", vocab_size=V, d_model=32,
                        n_heads=2, n_layers=2, max_len=T,
                        adapter_rank=rank)


def drill(rank):
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=2,
                    comm_round=2, epochs=1, batch_size=B, lr=0.1, seed=0,
                    adapter_rank=rank)
    srv = FedML_FedBuff_distributed(mk(rank), fed, None, cfg,
                                    wire_codec="topk0.1+int8",
                                    loopback_wire="tensor", buffer_k=2,
                                    loss_fn=loss)
    h = srv.final_health
    assert h["codec_refusals"] == 0, h
    return srv, h["bytes_rx"] / max(len(srv.arrival_log), 1)


dense_srv, dense_bpu = drill(0)     # the dense-delta codec point
srv, adapter_bpu = drill(8)         # adapter-only deltas, same codec
assert adapter_bpu < 0.5 * dense_bpu, (adapter_bpu, dense_bpu)
# Frozen base: bitwise-identical to the deterministic init.
ref = adapter_model_fns(mk(8))
ref.init(jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))
for a, b in zip(jax.tree.leaves(ref.holder["base"]),
                jax.tree.leaves(srv.adapter_holder["base"])):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print(f"adapter smoke OK: {adapter_bpu:.0f}B/upload vs dense-delta "
      f"{dense_bpu:.0f}B, base frozen, codec_refusals=0")
PYEOF

echo "== serve smoke (requests during a FedBuff run; rank-0 row == dense) =="
python - <<'PYEOF'
import math
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedbuff import FedML_FedBuff_distributed
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.models.adapter import PersonalAdapterStore, adapter_model_fns
from fedml_tpu.models.registry import create_model
from fedml_tpu.serve import ServeForward, ServeManager
from fedml_tpu.trainer.local import NetState, model_fns, seq_softmax_ce

V, T, B = 64, 16, 4
rng = np.random.RandomState(0)
seqs = rng.randint(1, V, size=(32, T + 1))
fed = build_federated_arrays(seqs[:, :T].astype(np.int32),
                             seqs[:, 1:].astype(np.int32),
                             partition_homo(32, 4), B)


def mk(rank):
    return create_model("transformer_lm", vocab_size=V, d_model=32,
                        n_heads=2, n_layers=2, max_len=T,
                        adapter_rank=rank)


# The serve plane over the SAME deterministic frozen base the trainer
# uses (seed 0 — base bitwise identity is pinned by the adapter smoke).
fns = adapter_model_fns(mk(4))
glob0 = fns.init(jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)).params
fwd = ServeForward(fns, glob0)
store = PersonalAdapterStore(32, glob0)
mgr = ServeManager(fwd, store, glob0, seq_len=T, max_batch=8,
                   deadline_s=0.005, queue_cap=64).start()
probe = rng.randint(1, V, T).astype(np.int32)
mgr.request(0, probe)  # warm the one compiled [8, T] shape

# 2-aggregation FedBuff run in the background; requests ride DURING it.
result = {}
cfg = FedConfig(client_num_in_total=4, client_num_per_round=2,
                comm_round=2, epochs=1, batch_size=B, lr=0.1, seed=0,
                adapter_rank=4)
trainer = threading.Thread(target=lambda: result.update(
    srv=FedML_FedBuff_distributed(mk(4), fed, None, cfg,
                                  loopback_wire="tensor", buffer_k=2,
                                  loss_fn=partial(seq_softmax_ce,
                                                  pad_id=0))))
trainer.start()
during = 0
while trainer.is_alive() and during < 48:
    mgr.request(int(during % 32), probe)
    during += 1
trainer.join()

# Publish the trained globals to the plane, then pin the identity
# invariant on the read path: a client with a ZERO (rank-0) adapter row
# serves the DENSE model's logits over the same frozen base, at the
# plane's own [8, T] batch shape (to the last ulp since jaxlib 0.9.0 —
# tests/test_serve.py::test_rank0_rows_equal_dense_model says why).
mgr.set_live(1, result["srv"].net.params)
store.scatter([7], np.zeros((1, fwd.dim), np.float32))
logits, _ = mgr.request(7, probe)
dense_fns = model_fns(mk(0))
base = fns.holder["base"]


def dense_row(tok):
    out, _ = dense_fns.apply(NetState(base, {}), tok[None], train=False)
    return out[0]


padded = np.zeros((8, T), np.int32)
padded[0] = probe
dense = np.asarray(jax.jit(jax.vmap(dense_row))(jnp.asarray(padded)))[0]
np.testing.assert_allclose(np.asarray(logits), dense, rtol=2e-6, atol=2e-6,
                           err_msg="rank-0 row != dense")

stats = mgr.stats()
mgr.close()
p95 = stats.get("serve/latency_ms_p95")
assert p95 is not None and math.isfinite(p95), stats
assert stats.get("serve/refused", 0) == 0, stats
assert stats.get("serve/shed", 0) == 0, stats
assert stats.get("serve/served", 0) >= during + 2, stats
print(f"serve smoke OK: {during} requests during training, "
      f"p95={p95:.1f}ms, refused=0 shed=0, rank-0 row == dense model")
PYEOF

echo "== parallel ingest pool: workers=2 bit-equal to workers=1 + pool spans =="
python - <<'PYEOF'
import json, os, tempfile
import numpy as np, jax
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg_distributed import FedML_FedAvg_distributed
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression

x, y = make_classification(240, n_features=16, n_classes=4, seed=1)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)

def run(workers, trace_dir=None):
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, epochs=2, batch_size=16, lr=0.3,
                    frequency_of_the_test=1, ingest_workers=workers)
    return FedML_FedAvg_distributed(
        LogisticRegression(num_classes=4), fed, test, cfg,
        wire_codec="topk0.25+int8", loopback_wire="tensor",
        trace_dir=trace_dir)

with tempfile.TemporaryDirectory() as td:
    a1 = run(1)
    a2 = run(2, trace_dir=td)
    # The pooled fixed-point fold is associative-exact: any worker count
    # lands the bit-identical final net regardless of loopback's
    # thread-scheduled arrival order.
    for l1, l2 in zip(jax.tree.leaves(a1.net), jax.tree.leaves(a2.net)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    prof = a2.ingest_profile
    assert prof["ingest_pool"]["workers"] == 2, prof
    # Every pool worker traced its tasks: nonzero per-worker span count.
    chrome = json.load(open(os.path.join(td, "trace.chrome.json")))
    per_worker = {}
    for e in chrome["traceEvents"]:
        if e["name"] == "ingest.pool":
            per_worker[e["args"]["worker"]] = \
                per_worker.get(e["args"]["worker"], 0) + 1
    assert per_worker and all(n > 0 for n in per_worker.values()), per_worker
    assert sum(per_worker.values()) == 8  # 2 rounds x 4 uploads
print(f"ingest pool smoke OK: bit-equal nets, pool spans {per_worker}")
PYEOF

echo "== sharded aggregation plane: M=2 bit-equal to M=1 + forced eviction =="
python - <<'PYEOF'
import json, os, tempfile
import numpy as np, jax
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg_distributed import (FedAVGAggregator,
                                                FedML_FedAvg_distributed)
from fedml_tpu.comm.loopback import LoopbackNetwork
from fedml_tpu.comm.shardplane import (AggregatorShardManager,
                                       ShardedFedAVGServerManager)
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression

x, y = make_classification(240, n_features=16, n_classes=4, seed=1)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)

def run(m):
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, epochs=2, batch_size=16, lr=0.3,
                    frequency_of_the_test=1)
    return FedML_FedAvg_distributed(
        LogisticRegression(num_classes=4), fed, test, cfg,
        wire_codec="topk0.25+int8", loopback_wire="tensor", agg_shards=m)

a1, a2 = run(1), run(2)
# The coordinator wire-merges the shards' int64 partials through the
# same division site as the in-process pool: any M lands the
# bit-identical net for the same arrivals.
for l1, l2 in zip(jax.tree.leaves(a1.net), jax.tree.leaves(a2.net)):
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
h = a2.final_health
assert h["shards"] == 2 and h["shard_evictions"] == 0, h
assert h["bytes_rx"] > 0, h  # per-shard ByteLedger totals rolled up

# Forced shard eviction (fake-clock protocol drive): shard 2 goes
# silent past the heartbeat deadline — the coordinator evicts it and
# the flight recorder persists the postmortem event.
with tempfile.TemporaryDirectory() as td:
    t = [0.0]
    class A: pass
    a = A(); a.network = LoopbackNetwork(7)
    scfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                     comm_round=2, frequency_of_the_test=1000)
    sagg = FedAVGAggregator({"w": np.zeros(8, np.float32)}, 4, scfg)
    srv = ShardedFedAVGServerManager(a, sagg, scfg, 7, 2,
                                     round_timeout_s=10.0,
                                     clock=lambda: t[0], flight_dir=td)
    shards = {r: AggregatorShardManager(a, r, 7, scfg,
                                        {"w": np.zeros(8, np.float32)},
                                        beat_interval_s=0.0,
                                        clock=lambda: t[0])
              for r in (1, 2)}
    for mgr in [srv, *shards.values()]:
        mgr.register_message_receive_handlers()
    srv.send_init_msg()
    t[0] = 99.0
    srv.shard_heartbeat.beat(1)
    srv._post_shard_tick([2])
    for rank, mgr in [(0, srv), (1, shards[1]), (2, shards[2])]:
        q = a.network.inbox(rank)
        while not q.empty():
            msg = q.get()
            if hasattr(msg, "get_type"):
                mgr.receive_message(msg.get_type(), msg)
    assert srv.shard_evictions == 1 and srv.health()["shards"] == 1
    fr = [json.loads(l)
          for l in open(os.path.join(td, "flight_recorder.jsonl"))]
    assert any(e["kind"] == "shard_eviction" for e in fr)
print(f"shard plane smoke OK: M=2 bit-equal to M=1 "
      f"(rx={h['bytes_rx']}B over {h['shards']} shards), forced "
      "eviction flight-recorded")
PYEOF

echo "== obs smoke: flight recorder + span trace + ingest histograms =="
python - <<'PYEOF'
import json, os, tempfile
import numpy as np
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg_distributed import (
    MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, FedAVGAggregator,
    FedAVGServerManager, FedML_FedAvg_distributed)
from fedml_tpu.comm.codec import CODEC_KEY, make_wire_codec
from fedml_tpu.comm.loopback import LoopbackNetwork
from fedml_tpu.comm.message import Message
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.obs import MetricsLogger

x, y = make_classification(240, n_features=16, n_classes=4, seed=1)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)
cfg = FedConfig(client_num_in_total=4, client_num_per_round=4, comm_round=2,
                epochs=1, batch_size=16, lr=0.3, frequency_of_the_test=1)
with tempfile.TemporaryDirectory() as td:
    # 2-round loopback codec drill with --trace semantics on
    metrics = MetricsLogger.for_run(run_dir=td, stdout=False)
    agg = FedML_FedAvg_distributed(
        LogisticRegression(num_classes=4), fed, test, cfg,
        wire_codec="topk0.25+int8", loopback_wire="tensor",
        metrics=metrics, trace_dir=td)
    metrics.close()
    # the Chrome trace-event JSON parses and holds the upload lifecycle
    chrome = json.load(open(os.path.join(td, "trace.chrome.json")))
    names = {e["name"] for e in chrome["traceEvents"]}
    assert {"client.train", "client.serialize", "ingest.decode",
            "ingest.fold", "round.commit"} <= names, names
    # metrics.jsonl carries the per-round ctrl/ ingest histograms
    rows = [json.loads(l) for l in open(os.path.join(td, "metrics.jsonl"))]
    ctrl = [r for r in rows if "ctrl/decode_ms_p50" in r]
    assert ctrl and all("ts" in r for r in rows), rows[:1]
    prof = agg.ingest_profile
    assert prof["uploads"] == 8 and prof["ingest_occupancy"] is not None
    # forced eviction (fake-clock protocol drive, corrupt codec frame):
    # the flight-recorder file must appear with the refusal + eviction
    class A: pass
    a = A(); a.network = LoopbackNetwork(3)
    scfg = FedConfig(client_num_in_total=2, client_num_per_round=2,
                     comm_round=2, frequency_of_the_test=1000)
    sagg = FedAVGAggregator({"w": np.zeros(8, np.float32)}, 2, scfg)
    srv = FedAVGServerManager(a, sagg, scfg, 3, flight_dir=td)
    good, _ = make_wire_codec("int8").encode({"w": np.ones(8, np.float32)},
                                             None, 1)
    bad = dict(good); bad["q"] = bad["q"][:2]
    m = Message(MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, 1, 0)
    m.add(Message.MSG_ARG_KEY_MODEL_PARAMS, bad)
    m.add(Message.MSG_ARG_KEY_NUM_SAMPLES, 10)
    m.add("round", 0); m.add(CODEC_KEY, "int8")
    srv.handle_message_receive_model_from_client(m)
    fr = [json.loads(l)
          for l in open(os.path.join(td, "flight_recorder.jsonl"))]
    kinds = {e["kind"] for e in fr}
    assert {"codec_refusal", "eviction"} <= kinds, kinds
print("obs smoke OK: trace parsed, ctrl/ histograms live, "
      "flight recorder dumped on forced eviction")
PYEOF

echo "== secure aggregation: masked M=2 bit-equal to unmasked + seed reveal =="
python - <<'PYEOF'
import json, os, tempfile, time
import numpy as np, jax
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg_distributed import (FedAVGAggregator,
                                                FedAVGClientManager,
                                                FedAVGServerManager,
                                                FedML_FedAvg_distributed,
                                                build_federation_setup)
from fedml_tpu.comm.loopback import run_workers
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.trainer.local import softmax_ce

x, y = make_classification(240, n_features=16, n_classes=4, seed=1)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)

def run(masked):
    cfg = FedConfig(client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, epochs=2, batch_size=16, lr=0.3,
                    frequency_of_the_test=1, secagg=masked)
    return FedML_FedAvg_distributed(
        LogisticRegression(num_classes=4), fed, test, cfg,
        wire_codec="topk0.25+int8", loopback_wire="tensor", agg_shards=2)

plain, masked = run(False), run(True)
# Pairwise seed-expanded masks live in the SAME fixed-point int64
# domain the shards fold, so they cancel exactly in the wire-merged
# sum: the masked federation lands the bit-identical net.
for l1, l2 in zip(jax.tree.leaves(plain.net), jax.tree.leaves(masked.net)):
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
h = masked.final_health
assert h["shards"] == 2 and h.get("seed_reveals", 0) == 0, h

# Forced mid-round dropout: rank 1's local step outlasts the round
# deadline and its beats stop — the watchdog evicts it, >=t survivors
# return Shamir shares of its seeds, the orphaned masks are subtracted
# and the round commits over survivors; the reveal is flight-recorded.
with tempfile.TemporaryDirectory() as td:
    cfgd = FedConfig(client_num_in_total=4, client_num_per_round=4,
                     comm_round=3, epochs=1, batch_size=16, lr=0.3,
                     frequency_of_the_test=10 ** 6, ingest_workers=1,
                     heartbeat_interval_s=0.05, secagg=True)
    size, net0, local_train, eval_fn, args = build_federation_setup(
        LogisticRegression(num_classes=4), fed, None, cfgd, "LOOPBACK",
        softmax_ce)
    srv = FedAVGServerManager(args, FedAVGAggregator(net0, size - 1, cfgd),
                              cfgd, size, round_timeout_s=1.5,
                              heartbeat_timeout_s=0.4, flight_dir=td)

    def victim_train(*a, **kw):
        if srv.round_idx >= 1:
            time.sleep(3.5)  # outlast the 1.5s round deadline
        return local_train(*a, **kw)

    clients = [FedAVGClientManager(args, r, size, fed,
                                   (victim_train if r == 1
                                    else local_train), cfgd)
               for r in range(1, size)]

    def killer():
        deadline = time.monotonic() + 20.0
        while srv.round_idx < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        clients[0].finish()  # beats stop: the watchdog owns it now

    run_workers([srv.run] + [c.run for c in clients] + [killer])
    assert not srv.aborted and srv.seed_reveals >= 1, \
        (srv.aborted, srv.seed_reveals)
    assert srv.health()["evictions"] >= 1
    fr = [json.loads(l)
          for l in open(os.path.join(td, "flight_recorder.jsonl"))]
    kinds = {e["kind"] for e in fr}
    assert "seed_reveal" in kinds, kinds
print(f"secagg smoke OK: masked M=2 bit-equal to unmasked, dropout "
      f"recovered via {srv.seed_reveals} seed reveal(s), flight-recorded")
PYEOF

echo "== async FL (no-barrier staleness-weighted) =="
python -m fedml_tpu.exp.main_extra --algorithm FedAsync \
    --model lr --dataset synthetic_1_1 $common

echo "== buffered semi-sync FL (aggregate every k arrivals, controller on) =="
python -m fedml_tpu.exp.main_extra --algorithm FedBuff --buffer_k 2 \
    --controller adaptive --model lr --dataset synthetic_1_1 $common

echo "== adaptive controller: spiked sim actuates; off-twin digest pinned =="
python - <<'PYEOF'
import hashlib, json, os, tempfile
from fedml_tpu.algos.config import FedConfig
from fedml_tpu.ctrl import (FederationController, StalenessAdmissionPolicy,
                            WindowSchedulePolicy)
from fedml_tpu.data.batching import batch_global, build_federated_arrays
from fedml_tpu.data.partition import partition_homo
from fedml_tpu.data.synthetic import make_classification
from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.sim import FleetSimulator, FleetSpec, make_fleet_trace

# Controller-off twin: the seeded fedbuff drill stays bit-identical to
# the pre-controller tree (tests/test_ctrl.py pins all three modes; this
# digest is the fedbuff one).
x, y = make_classification(160, n_features=8, n_classes=2, seed=3)
fed = build_federated_arrays(x, y, partition_homo(len(x), 4), batch_size=16)
test = batch_global(x[:64], y[:64], 16)
cfg = FedConfig(client_num_in_total=4, client_num_per_round=4, comm_round=12,
                epochs=1, batch_size=16, lr=0.3, frequency_of_the_test=4)
spec = FleetSpec(n_devices=4, seed=5, horizon_s=4000.0, mean_online=0.8,
                 base_round_s=25.0, slot_s=150.0)
res = FleetSimulator(LogisticRegression(num_classes=2), fed, test, cfg,
                     make_fleet_trace(spec), mode="fedbuff",
                     buffer_k=2).run()
digest = hashlib.sha256(repr(
    (res.arrival_log, res.staleness, res.updates, round(res.virtual_s, 3),
     [round(t, 3) for t in res.completion_times])).encode()).hexdigest()
GOLDEN = "e2b90d4c28ed5e1e0efd6ccf5c79088535fd77ef6781a46b1bbbdeadd8dd433b"
assert digest == GOLDEN, f"controller-off drift: {digest}"

# Forced load spike: the guard-band admission policy must actuate
# through the seam, and the actuation must land in the on-disk flight
# dump (the postmortem artifact an operator reads after a bad night).
sx, sy = make_classification(320, n_features=10, n_classes=4, seed=1)
sfed = build_federated_arrays(sx, sy, partition_homo(len(sx), 8),
                              batch_size=16)
stest = batch_global(sx[:96], sy[:96], 16)
scfg = FedConfig(client_num_in_total=8, client_num_per_round=8,
                 comm_round=12, epochs=1, batch_size=16, lr=0.3,
                 frequency_of_the_test=4)
sspec = FleetSpec(n_devices=8, seed=11, horizon_s=20000.0, mean_online=0.92,
                  base_round_s=20.0, slot_s=400.0, arrival_spread_s=30.0,
                  spike_t0=250.0, spike_t1=700.0, spike_factor=6.0)
ctl = FederationController(
    [WindowSchedulePolicy(w_min=1, w_max=4),
     StalenessAdmissionPolicy(band_lo=2.0, band_hi=4.0, k_max=4,
                              cap_slack=0, cooldown=2)], interval=1)
with tempfile.TemporaryDirectory() as td:
    sim = FleetSimulator(LogisticRegression(num_classes=4), sfed, stest,
                         scfg, make_fleet_trace(sspec), mode="fedbuff",
                         buffer_k=2, controller=ctl)
    sim.server.flight.path = os.path.join(td, "flight_recorder.jsonl")
    sim.run()
    applied = [e for e in ctl.actuation_log if e["outcome"] == "applied"
               and e["policy"] == "staleness_admission"]
    assert applied, ctl.actuation_log
    snap = sim.server.registry.snapshot()
    assert snap.get("actuation_applied", 0) >= 1, snap
    fr = [json.loads(l) for l in open(sim.server.flight.path)]
    assert any(e["kind"] == "actuation" for e in fr), {e["kind"] for e in fr}
print(f"controller smoke OK: off-twin digest pinned, spike drew "
      f"{len(applied)} admission actuation(s), flight-recorded on disk")
PYEOF

echo "== message-passing framework templates =="
python -m fedml_tpu.exp.main_extra --algorithm BaseFramework $common

echo "== vertical FL (synthetic NUS-WIDE-shaped two-party data) =="
python -m fedml_tpu.exp.main_extra --algorithm VFL $common

echo "CI OK"
