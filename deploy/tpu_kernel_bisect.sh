#!/bin/bash
# Per-kernel hardware check: each case of deploy/tpu_kernel_bisect.py in
# its own process, bounded by `timeout`, compared with its registry
# oracle. What to run when chip_smoke.py fails inside a kernel: a case
# that fails to compile, hangs on a DMA semaphore or computes garbage is
# named, and a hang costs one case's timeout instead of the whole call.
#
# One chip belongs to one process at a time, so the cases run one after
# another and this script itself never touches JAX.
#
#   1. a matmul probe — is there a TPU at all? (no TPU is exit 2, never a
#      CPU run);
#   2. every case, or the ones named; a case that fails is reported and
#      the rest still run, but a case that times out stops the script,
#      because a kernel that hung may have left the chip unusable.
#
# Usage: deploy/tpu_kernel_bisect.sh [logdir] [case ...]
# Through the chip tool (logs come back under chiprun_out/):
#   chiprun -- bash deploy/tpu_kernel_bisect.sh
# Exit codes: 0 every case passed; 2 no TPU; 3 a case failed or timed out
# (see $logdir/bisect_<case>.log).
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

LOGDIR="${1:-chiprun_out/bisect}"
[[ $# -gt 0 ]] && shift
mkdir -p "$LOGDIR"

PY=${PYTHON:-python}
PROBE_TIMEOUT=${PROBE_TIMEOUT:-120}
KERNEL_TIMEOUT=${KERNEL_TIMEOUT:-300}

say() { echo "[$(date -u +%H:%M:%S)] $*" | tee -a "$LOGDIR/bisect.log"; }

probe() {
  timeout "$PROBE_TIMEOUT" "$PY" -c "
import jax, jax.numpy as jnp
d = jax.devices()[0]
assert d.platform == 'tpu', f'no TPU: jax found {d.platform}'
x = jnp.ones((256, 256), jnp.bfloat16)
print('TPU-OK', float((x @ x).sum()), d.device_kind, len(jax.devices()))
" 2>&1 | tail -1
}

h=$(probe)
say "probe: $h"
if [[ "$h" != TPU-OK* ]]; then
  say "no usable TPU — not attempting kernels"
  exit 2
fi

if [[ $# -gt 0 ]]; then CASES="$*"; else
  CASES=$("$PY" deploy/tpu_kernel_bisect.py --list)
fi
FAILED=""
for k in $CASES; do
  timeout "$KERNEL_TIMEOUT" "$PY" deploy/tpu_kernel_bisect.py "$k" \
    > "$LOGDIR/bisect_$k.log" 2>&1
  rc=$?
  grep -E "OK  |FAIL|Error" "$LOGDIR/bisect_$k.log" | cut -c1-300 \
    | tee -a "$LOGDIR/bisect.log"
  say "case $k rc=$rc"
  [[ $rc -eq 0 ]] && continue
  FAILED="$FAILED $k"
  tail -15 "$LOGDIR/bisect_$k.log"
  if [[ $rc -eq 124 || $rc -eq 137 ]]; then
    # a kernel that hung may have left the chip unusable for the next
    say "case $k timed out — stopping"
    break
  fi
done
if [[ -n "$FAILED" ]]; then
  say "FAILED:$FAILED"
  exit 3
fi
say "all cases passed"
