"""Cross-silo CLI: 1 server + 2 silo OS processes on localhost (the
reference's mpirun regime, without mpirun), over the native TCP transport
and over the TRPC backend (acknowledged RPC sends, tensor wire)."""

import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["TCP", "TRPC"])
def test_cross_silo_three_processes(tmp_path, backend):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    # pid+backend-derived base so concurrent suite runs (and the two
    # backend variants) don't fight over rank ports
    port_base = 42000 + (os.getpid() % 2000) * 8 + (4 if backend == "TRPC" else 0)
    common = [
        sys.executable, "-m", "fedml_tpu.exp.main_cross_silo",
        "--size", "3", "--port_base", str(port_base),
        "--comm_backend", backend,
        "--model", "lr", "--dataset", "synthetic_1_1",
        "--client_num_in_total", "6", "--batch_size", "8",
        "--comm_round", "3", "--epochs", "1", "--lr", "0.2",
        "--frequency_of_the_test", "1",
    ]
    procs = [
        subprocess.Popen(common + ["--rank", str(r)], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for r in range(3)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, err[-2000:]
    server_line = json.loads(outs[0][1].strip().splitlines()[-1])
    assert server_line["rank"] == 0
    assert "accuracy" in server_line
    assert server_line["accuracy"] > 0.15  # learned something over 3 rounds
