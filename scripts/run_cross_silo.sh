#!/bin/bash
# Launch a full cross-silo federation on one machine: 1 server + W silo
# OS processes over the native TCP transport (or gRPC).
#
# Role parity with the reference's mpirun wrappers
# (fedml_experiments/distributed/fedavg/run_fedavg_distributed_pytorch.sh:21
# does `mpirun -np $PROCESS_NUM ... python3 ./main_fedavg.py`): same
# one-command launch, no MPI required — each rank is a plain python
# process and the rank table is ports, not a hostfile.
#
# Placement: this launcher is the one-machine demo of the MESSAGE plane,
# and every rank it starts runs on the CPU (JAX_PLATFORMS=cpu, printed
# below). A chip belongs to one process at a time: W+1 processes on the
# default backend of a one-chip host would hand the chip to the first
# rank and fail or hang the rest. To train a silo on a chip, start
# `python -m fedml_tpu.exp.main_cross_silo --rank R ...` by hand on that
# silo's own host — it takes the host's chips or fails.
#
# Usage:
#   scripts/run_cross_silo.sh <num_silos> [extra main_cross_silo args...]
# Example:
#   scripts/run_cross_silo.sh 3 --model lr --dataset mnist \
#       --comm_round 10 --epochs 1 --lr 0.1 --comm_backend GRPC
set -euo pipefail

W=${1:?usage: run_cross_silo.sh <num_silos> [args...]}
shift
SIZE=$((W + 1))
PORT_BASE=${PORT_BASE:-50100}

export JAX_PLATFORMS=cpu
echo "run_cross_silo.sh: $SIZE ranks on this machine, all JAX_PLATFORMS=cpu" >&2

pids=()
for rank in $(seq 1 "$W"); do
    python -m fedml_tpu.exp.main_cross_silo \
        --rank "$rank" --size "$SIZE" --port_base "$PORT_BASE" "$@" &
    pids+=($!)
done
# Server in the foreground: its JSON summary line is this script's output.
python -m fedml_tpu.exp.main_cross_silo \
    --rank 0 --size "$SIZE" --port_base "$PORT_BASE" "$@"
status=0
for pid in "${pids[@]}"; do
    wait "$pid" || status=$?
done
exit "$status"
