"""Attribute the s2d round's time to fwd / bwd / GN / optimizer+agg.

r3 VERDICT #2: the s2d stem variant measures ~6% MFU against a ~26%
lane-fill ceiling and the residual was closed by conjecture ("bwd-pass
layout tuning and GN fusion") rather than measurement. This script times,
at the exact s2d bench config (8 vmapped clients x 256 samples, B=32,
bf16, 1 local epoch = 8 SGD steps/client):

  full       — the shipped round_fn (fwd+bwd+SGD+shuffle+aggregation)
  fwd_only   — per-step masked loss, no grad (params perturbed by
               eps*loss to defeat loop-invariant hoisting)
  fwd_bwd    — value_and_grad per step, update = p - eps*g (an axpy,
               cost-identical to the real SGD step, so fwd_bwd isolates
               gradient cost, not optimizer cost)
  agg_only   — tree_weighted_mean over the 8 client param stacks
  full_nogn  — full round with Norm swapped for identity (norm="none")
  full_noshuf— full round with the per-epoch reshuffle disabled

and prints a table whose rows decompose the measured round time:
bwd = fwd_bwd - fwd_only, GN = full - full_nogn, shuffle = full -
full_noshuf, plumbing residual = full - fwd_bwd - agg_only.

All timings are chained iterations inside one jit with a DYNAMIC trip
count (no recompile across chain lengths), calibrated per variant so a
timed call carries >=0.4 s of device work — the same machinery as
bench.py's flash sweep (two-point fit cancels the fixed per-call
dispatch cost).
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from fedml_tpu.models.resnet import resnet56
from fedml_tpu.trainer.local import (NetState, make_local_train_fn,
                                     model_fns, softmax_ce)
from fedml_tpu.parallel.shard import make_vmap_round, client_rngs
from fedml_tpu.core.tree import tree_weighted_mean
import optax

C, S, B = 8, 8, 32          # clients, steps/client, batch
SAMPLES = C * S * B          # per round
FLOOR_S, TARGET_S = 0.4, 0.6
EPS = 1e-38


def calibrated(f, *args):
    """Median seconds/iter of f(*args, iters) with the floor enforced.
    A host scalar fetch ends every call (the sync bench.py trusts); the
    two-point fit cancels the fixed per-call dispatch cost."""
    def call(iters):
        t0 = time.perf_counter()
        out = f(*args, iters)
        float(jnp.asarray(jax.tree.leaves(out)[0]).ravel()[0])
        return time.perf_counter() - t0

    call(1)  # warm/compile
    t1 = min(call(1) for _ in range(2))
    t2 = min(call(5) for _ in range(2))
    per_iter = max((t2 - t1) / 4, 1e-4)
    rtt = max(t1 - per_iter, 0.0)
    for _ in range(4):
        iters = max(1, min(1 << 17, int(np.ceil(TARGET_S / per_iter))))
        meds = sorted(call(iters) for _ in range(5))
        med = meds[2]
        refined = max((med - rtt) / iters, 1e-4)
        if refined * iters >= FLOOR_S:
            return refined
        per_iter = refined
    raise RuntimeError("floor not reached")


def make_data():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(C, S, B, 32, 32, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, (C, S, B)), jnp.int32)
    mask = jnp.ones((C, S, B), jnp.float32)
    w = jnp.ones((C,), jnp.float32)
    return x, y, mask, w


def chain_round(round_fn):
    """Chained full rounds: avg params feed the next round."""
    def run(net, x, y, mask, w, rng, iters):
        def body(i, carry):
            net, rng = carry
            rng, sub = jax.random.split(rng)
            avg, loss = round_fn(net, x, y, mask, w, w, sub)
            return avg, rng
        net, _ = jax.lax.fori_loop(0, iters, body, (net, rng))
        return net.params
    return jax.jit(run)


def chain_clients(client_fn):
    """Chained vmapped per-client passes over a STACKED per-client net
    (the carry stays [C, ...]-shaped across iterations — no aggregation
    in this variant, that is ``agg_only``'s job); params perturbed by
    the pass's output so iterations stay sequentially dependent."""
    def run(net_stacked, x, y, mask, rng, iters):
        def body(i, carry):
            net, rng = carry
            rng, sub = jax.random.split(rng)
            rngs = client_rngs(sub, C, 0)
            new_net = jax.vmap(client_fn)(net, x, y, mask, rngs)
            return new_net, rng
        net, _ = jax.lax.fori_loop(0, iters, body, (net_stacked, rng))
        return net.params
    return jax.jit(run)


def main():
    fns = model_fns(resnet56(num_classes=10, dtype="bf16", stem="s2d"))
    fns_nogn = model_fns(resnet56(num_classes=10, dtype="bf16", stem="s2d",
                                  norm="none"))
    x, y, mask, w = make_data()
    key = jax.random.PRNGKey(0)
    net = fns.init(key, np.zeros((B, 32, 32, 3), np.float32))
    net_nogn = fns_nogn.init(key, np.zeros((B, 32, 32, 3), np.float32))
    opt = optax.sgd(0.1)

    results = {}

    def full_round(fns_, shuffle=True):
        lt = make_local_train_fn(fns_.apply, opt, 1, softmax_ce,
                                 shuffle=shuffle)
        return make_vmap_round(lt)

    fns_fused = model_fns(resnet56(num_classes=10, dtype="bf16",
                                   stem="s2d", norm="gn_fused"))
    # gn and gn_fused share param trees (same names/shapes), so the
    # fused variant reuses net — an identical-numerics A/B.
    # --- full round variants -------------------------------------------
    for name, fns_, n0, shuf in [("full", fns, net, True),
                                 ("full_fusedgn", fns_fused, net, True),
                                 ("full_nogn", fns_nogn, net_nogn, True),
                                 ("full_noshuf", fns, net, False)]:
        f = chain_round(full_round(fns_, shuf))
        results[name] = calibrated(f, n0, x, y, mask, w, key)
        print(f"{name:12s} {results[name]*1e3:8.2f} ms/round "
              f"({SAMPLES/results[name]:,.0f} samples/s)", flush=True)

    # --- fwd-only ------------------------------------------------------
    def fwd_client(net, cx, cy, cmask, rng):
        def step(carry, inp):
            net, rng = carry
            xb, yb, mb = inp
            rng, sub = jax.random.split(rng)
            logits, new_state = fns.apply(net, xb, train=True, rng=sub)
            per = softmax_ce(logits, yb)
            loss = jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb), 1.0)
            # eps*loss keeps iterations sequentially dependent without
            # changing numerics (denormal-scale perturbation)
            p = jax.tree.map(lambda a: a + EPS * loss, net.params)
            return (NetState(p, new_state), rng), loss
        (net, _), _ = jax.lax.scan(step, (net, rng), (cx, cy, cmask))
        return net

    net_stacked = jax.tree.map(
        lambda p: jnp.stack([p] * C),
        NetState(net.params, net.model_state))
    results["fwd_only"] = calibrated(chain_clients(fwd_client),
                                     net_stacked, x, y, mask, key)
    print(f"{'fwd_only':12s} {results['fwd_only']*1e3:8.2f} ms/round",
          flush=True)

    # --- fwd+bwd (grad, axpy update, no optimizer state) ---------------
    def grad_client(net, cx, cy, cmask, rng):
        def step(carry, inp):
            net, rng = carry
            xb, yb, mb = inp
            rng, sub = jax.random.split(rng)

            def masked_loss(p):
                logits, new_state = fns.apply(
                    NetState(p, net.model_state), xb, train=True, rng=sub)
                per = softmax_ce(logits, yb)
                return (jnp.sum(per * mb)
                        / jnp.maximum(jnp.sum(mb), 1.0)), new_state

            (loss, new_state), g = jax.value_and_grad(
                masked_loss, has_aux=True)(net.params)
            p = jax.tree.map(lambda a, b: a - EPS * b, net.params, g)
            return (NetState(p, new_state), rng), loss
        (net, _), _ = jax.lax.scan(step, (net, rng), (cx, cy, cmask))
        return net

    results["fwd_bwd"] = calibrated(chain_clients(grad_client),
                                    net_stacked, x, y, mask, key)
    print(f"{'fwd_bwd':12s} {results['fwd_bwd']*1e3:8.2f} ms/round",
          flush=True)

    # --- aggregation only ---------------------------------------------
    stacked = jax.tree.map(lambda p: jnp.stack([p] * C), net.params)

    def agg(stacked, w, iters):
        def body(i, st):
            avg = tree_weighted_mean(st, w * (1 + EPS * i))
            return jax.tree.map(lambda s, a: s + EPS * a, st, avg)
        return jax.tree.leaves(jax.lax.fori_loop(0, iters, body, stacked))[0]

    results["agg_only"] = calibrated(jax.jit(agg), stacked, w)
    print(f"{'agg_only':12s} {results['agg_only']*1e3:8.2f} ms/round",
          flush=True)

    # --- the bench path: sampling + cohort gather + whole-run scan -----
    # (what `bench_resnet56_s2d` actually times). Two-point fit over scan
    # lengths cancels the RTT + scan entry cost; the difference vs `full`
    # is the per-round price of on-device subsampled cohort gathering.
    import bench as bench_mod

    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.algos.fedavg import FedAvgAPI

    fed = bench_mod._synthetic_cifar_fed(128, 256, B)
    cfg = FedConfig(client_num_in_total=128, client_num_per_round=C,
                    comm_round=1, epochs=1, batch_size=B, lr=0.1)
    api = FedAvgAPI(resnet56(num_classes=10, dtype="bf16", stem="s2d"),
                    fed, None, cfg)

    def scan_time(r):
        api.train_rounds_on_device(r)  # compile + warm
        vals = []
        for _ in range(3):
            t0 = time.perf_counter()
            losses = api.train_rounds_on_device(r)
            float(np.asarray(losses).sum())
            vals.append(time.perf_counter() - t0)
        return sorted(vals)[1]

    r1, r2 = 8, 24
    results["bench_path"] = (scan_time(r2) - scan_time(r1)) / (r2 - r1)
    print(f"{'bench_path':12s} {results['bench_path']*1e3:8.2f} ms/round "
          f"({SAMPLES/results['bench_path']:,.0f} samples/s)", flush=True)

    # --- decomposition table ------------------------------------------
    R, F, G = results["full"], results["fwd_only"], results["fwd_bwd"]
    A = results["agg_only"]
    print("\n=== decomposition (ms/round) ===")
    rows = [
        ("forward", F * 1e3, F / R),
        ("backward (fwd_bwd - fwd)", (G - F) * 1e3, (G - F) / R),
        ("aggregation", A * 1e3, A / R),
        ("optimizer+shuffle+plumbing (residual)", (R - G - A) * 1e3,
         (R - G - A) / R),
        ("TOTAL (= full round)", R * 1e3, 1.0),
    ]
    for name, ms, frac in rows:
        print(f"{name:40s} {ms:8.2f} ms  {frac*100:5.1f}%")
    print("\n=== ablations (ms/round) ===")
    print(f"{'GN cost (full - full_nogn)':40s} "
          f"{(R - results['full_nogn'])*1e3:8.2f} ms "
          f"{(R - results['full_nogn'])/R*100:5.1f}%")
    print(f"{'shuffle cost (full - full_noshuf)':40s} "
          f"{(R - results['full_noshuf'])*1e3:8.2f} ms "
          f"{(R - results['full_noshuf'])/R*100:5.1f}%")
    bp = results["bench_path"]
    print(f"{'cohort gather+scan (bench_path - full)':40s} "
          f"{(bp - R)*1e3:8.2f} ms {(bp - R)/bp*100:5.1f}% of bench round")
    print(f"\nfull round: {SAMPLES/R:,.0f} samples/s; bench path: "
          f"{SAMPLES/bp:,.0f} samples/s; fwd:bwd ratio 1:{(G-F)/F:.2f}")


if __name__ == "__main__":
    main()
