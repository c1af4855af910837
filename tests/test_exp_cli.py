"""Experiments/CLI layer: reference-compatible flags drive real runs."""

import json
import subprocess
import sys

import numpy as np
import pytest

from fedml_tpu.exp import parse_args, round_lr, run


def _args(extra=()):
    base = [
        "--model", "lr", "--dataset", "synthetic_1_1",
        "--client_num_in_total", "8", "--client_num_per_round", "8",
        "--batch_size", "8", "--comm_round", "3", "--epochs", "1",
        "--lr", "0.1", "--frequency_of_the_test", "2",
    ]
    return parse_args(base + list(extra))


@pytest.mark.parametrize("algo", ["FedAvg", "FedOpt", "FedProx", "FedNova", "FedAvgRobust", "FedAc"])
def test_run_algorithms(algo):
    api, history = run(_args(), algorithm=algo)
    assert len(history) == 3
    assert np.isfinite(history[-1]["train_loss"])
    assert "test_acc" in history[-1] or "acc" in history[-1] or len(history[-1]) > 2


def test_run_hierarchical():
    _, history = run(_args(["--group_num", "2"]), algorithm="HierarchicalFL")
    assert np.isfinite(history[-1]["train_loss"])


@pytest.mark.slow  # >8 s drill; tier-1 re-fit to the 870 s budget on the 1-core box (r16 audit)
def test_run_fedadapter():
    """The adapter finetune CLI (PR 15): transformer + NWP + LoRA rank —
    the frozen-base federation trains end to end from exp/run.py."""
    args = parse_args([
        "--model", "transformer_lm", "--dataset", "stackoverflow_nwp",
        "--adapter_rank", "4", "--client_num_in_total", "8",
        "--client_num_per_round", "4", "--batch_size", "4",
        "--comm_round", "2", "--epochs", "1", "--lr", "0.1", "--ci", "1"])
    api, history = run(args, algorithm="FedAdapter")
    assert np.isfinite(history[-1]["train_loss"])
    prof = api.adapter_profile()
    assert 0 < prof["adapter_ratio"] < 0.5


@pytest.mark.slow  # >20 s on the 2-core 870 s tier-1 budget box (r6 audit)

def test_run_sequence_dataset():
    args = parse_args([
        "--model", "rnn", "--dataset", "shakespeare",
        "--client_num_in_total", "4", "--client_num_per_round", "4",
        "--batch_size", "4", "--comm_round", "2", "--epochs", "1", "--lr", "0.5",
    ])
    _, history = run(args, algorithm="FedAvg")
    assert np.isfinite(history[-1]["train_loss"])


def test_run_with_mesh_and_schedule():
    _, history = run(
        _args(["--num_devices", "4", "--lr_schedule", "cosine", "--grad_clip", "1.0"])
    )
    assert np.isfinite(history[-1]["train_loss"])


def test_round_lr_quantization():
    lrs = {round_lr(0.1, "cosine", r, 100) for r in range(100)}
    assert len(lrs) <= 17  # 16 buckets + endpoint
    assert round_lr(0.1, "none", 50, 100) == 0.1
    assert round_lr(0.1, "step", 0, 100) == pytest.approx(0.1)


def test_cli_subprocess_north_star():
    """The reference-style launch command works end-to-end as a subprocess."""
    cmd = [
        sys.executable, "-m", "fedml_tpu.exp.main_fedavg",
        "--model", "lr", "--dataset", "synthetic_1_1",
        "--client_num_in_total", "6", "--client_num_per_round", "6",
        "--batch_size", "8", "--comm_round", "2", "--epochs", "1",
        "--ci", "1",
    ]
    import os

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "train_loss" in last


@pytest.mark.slow  # >20 s on the 2-core 870 s tier-1 budget box (r6 audit)

def test_run_fedseg_cli():
    args = parse_args([
        "--model", "unet", "--dataset", "synthetic_seg",
        "--client_num_in_total", "4", "--client_num_per_round", "4",
        "--batch_size", "8", "--comm_round", "2", "--epochs", "1",
        "--lr", "0.05", "--client_optimizer", "adam",
    ])
    _, history = run(args, algorithm="FedSeg")
    assert np.isfinite(history[-1]["train_loss"])
    assert "mIoU" in history[-1]


def test_centralized_cli_single_and_mesh_dp():
    """Centralized baseline CLI (reference fedml_experiments/centralized/
    main.py): trains on the pooled dataset, and the mesh data-parallel
    path (DDP equivalent, :376) matches the single-device run numerically
    — same function, batch axis sharded, GSPMD all-reduces grads."""
    import jax

    from fedml_tpu.exp.main_centralized import run_centralized

    base = [
        "--model", "lr", "--dataset", "synthetic_1_1",
        "--client_num_in_total", "8", "--batch_size", "8",
        "--comm_round", "3", "--epochs", "1", "--lr", "0.1",
        "--frequency_of_the_test", "2",
    ]
    t1, h1 = run_centralized(parse_args(base))
    t8, h8 = run_centralized(parse_args(base + ["--num_devices", "8"]))
    assert np.isfinite(h1[-1]["train_loss"])
    assert "accuracy" in h1[-1]
    np.testing.assert_allclose(h1[-1]["train_loss"], h8[-1]["train_loss"],
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(t1.net.params),
                    jax.tree.leaves(t8.net.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    # Batch size must divide the mesh.
    with pytest.raises(ValueError, match="divide"):
        run_centralized(parse_args(base[:-4] + [
            "--batch_size", "9", "--num_devices", "8", "--comm_round", "1"]))
