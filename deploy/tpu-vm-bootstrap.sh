#!/usr/bin/env bash
# Bootstrap a Cloud TPU VM as a GridLLM-TPU worker.
#
# Usage (on each TPU VM host):
#   REDIS_HOST=<bus-node> GRIDLLM_MODELS=llama3:8b \
#   GRIDLLM_CHECKPOINT_DIR=/data/checkpoints ./tpu-vm-bootstrap.sh
#
# A model that fits no single chip, on one four-chip host (measured:
# PERF.md, PR 27; the benchmark's cell nemo12b-tp4.chat): v5e-4,
#   GRIDLLM_MODELS=mistral-nemo:12b GRIDLLM_MESH_SHAPE=tp:4 \
#   GRIDLLM_MAX_BATCH_SLOTS=16 ./tpu-vm-bootstrap.sh
# 6.1 GB of weights + 5.4 GB of KV pool a chip (11.5 of 16.9 GB at peak);
# start to serving 116 s with an empty compile cache, 36 s with a warm one
# (keep JAX_COMPILATION_CACHE_DIR on a disk that survives restarts).
#
# Multi-host slices (e.g. v5e-16 across 2 hosts): run this on every host;
# jax.distributed coordination is derived from the TPU metadata when
# GRIDLLM_MULTIHOST=1 — only process 0 speaks to the Redis bus (the slice
# registers as ONE logical worker; see gridllm_tpu/parallel/mesh.py).
set -euo pipefail

REPO_DIR=${REPO_DIR:-$(cd "$(dirname "$0")/.." && pwd)}
VENV=${VENV:-$HOME/.gridllm-venv}

if ! command -v python3 >/dev/null; then
  echo "python3 required" >&2; exit 1
fi

python3 -m venv "$VENV" 2>/dev/null || true
source "$VENV/bin/activate"
pip install -q --upgrade pip

# TPU runtime: jax wheel + matching libtpu
pip install -q 'jax[tpu]' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
pip install -q "$REPO_DIR"

python - <<'EOF'
import jax
print("devices:", jax.devices())
assert any(d.platform == "tpu" for d in jax.devices()), "no TPU visible"
EOF

export GRIDLLM_BUS_URL=${GRIDLLM_BUS_URL:-resp://${REDIS_HOST:-localhost}:${REDIS_PORT:-6379}}
export GRIDLLM_MESH_SHAPE=${GRIDLLM_MESH_SHAPE:-tp:-1}

exec gridllm-worker
