#!/usr/bin/env bash
# Multi-host launcher (the reference's scripts/launch.sh torchrun wrapper,
# re-shaped for JAX multi-process: one process per host, coordinator env
# instead of torchrun rendezvous).
#
# Usage (run the SAME command on every host):
#   COORDINATOR=host0:8476 NPROC=4 PROC_ID=<this host idx> \
#       scripts/launch.sh python tests/... | examples/...
#
# On Cloud TPU pods the launcher env is usually injected already
# (JAX_COORDINATOR_ADDRESS etc.) — then just `python your_script.py`;
# this wrapper is for manual bring-up and matches the reference's
# env-plumbing role (NVSHMEM_*/NCCL_* ≙ JAX_*/TPU_* here).
set -euo pipefail

if [[ $# -lt 1 ]]; then
  echo "usage: COORDINATOR=host:port NPROC=n PROC_ID=i $0 <cmd...>" >&2
  exit 2
fi

# Coordinator plumbing (reference launch.sh reads ARNOLD_*/RANK env).
export JAX_COORDINATOR_ADDRESS="${COORDINATOR:-${JAX_COORDINATOR_ADDRESS:-}}"
export JAX_NUM_PROCESSES="${NPROC:-${JAX_NUM_PROCESSES:-1}}"
export JAX_PROCESS_ID="${PROC_ID:-${JAX_PROCESS_ID:-0}}"

# Sane defaults mirroring the reference's forced env
# (CUDA_DEVICE_MAX_CONNECTIONS=1, NVSHMEM_SYMMETRIC_SIZE):
#  - keep compilation cache on (first Mosaic compile is slow)
#  - un-filtered tracebacks for actionable crash reports
export JAX_TRACEBACK_FILTERING="${JAX_TRACEBACK_FILTERING:-off}"
if [[ -z "${JAX_COMPILATION_CACHE_DIR:-}" ]]; then
  # Same rule as triton_dist_tpu/runtime/compile_cache.py: a fixed path
  # beside the code unless the caller placed the cache.
  export JAX_COMPILATION_CACHE_DIR="$(cd "$(dirname "$0")/.." && pwd)/.jax_cache"
fi
export TDT_AUTOTUNE_CACHE="${TDT_AUTOTUNE_CACHE:-1}"

if [[ -n "${JAX_COORDINATOR_ADDRESS}" ]]; then
  echo "[launch] proc ${JAX_PROCESS_ID}/${JAX_NUM_PROCESSES}" \
       "coordinator ${JAX_COORDINATOR_ADDRESS}" >&2
else
  echo "[launch] single-host (no COORDINATOR set)" >&2
fi

exec "$@"
