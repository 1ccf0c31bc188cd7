#!/usr/bin/env bash
# Full distributed Groth16 prover over real mTLS sockets: generate per-rank
# certs, launch an 8-process star, wait for every rank, propagate failures.
# The reference's scripts/sha256.zsh role for nonlocal_sha256.rs:126.
# A CPU-backend test of the mTLS transport: eight OS processes cannot share
# one accelerator, so every rank is pinned to XLA:CPU (chip_smoke.py is the
# chip path).
#
#   ./scripts/nonlocal_sha256.sh                # chain circuit, fast smoke
#   CIRCUIT=sha256 ./scripts/nonlocal_sha256.sh # the full sha256 workload
#   PLAIN=1 ...                                 # plain TCP, no TLS
set -euo pipefail
cd "$(dirname "$0")/.."

N=${N:-8}
PORT=${PORT:-9785}
CIRCUIT=${CIRCUIT:-chain}
LOG2=${LOG2:-10}
WORK=${WORK_DIR:-$(mktemp -d)}
if [ -z "${WORK_DIR:-}" ]; then trap 'rm -rf "$WORK"' EXIT; fi

EXTRA=()
if [ "${PLAIN:-0}" = "1" ]; then
  EXTRA+=(--plain)
else
  for i in $(seq 0 $((N - 1))); do
    python -m distributed_groth16_tpu.utils.certs "$i" "$WORK/certs" >/dev/null
  done
fi

ADDR="$WORK/addresses"
for i in $(seq 0 $((N - 1))); do
  echo "127.0.0.1:$((PORT + i))" >> "$ADDR"
done

PIDS=()
for i in $(seq $((N - 1)) -1 0); do
  JAX_PLATFORMS=cpu python examples/nonlocal_sha256.py \
    --id "$i" --input "$ADDR" --certs "$WORK/certs" --n "$N" \
    --circuit "$CIRCUIT" --log2-constraints "$LOG2" "${EXTRA[@]}" \
    > "$WORK/rank$i.log" 2>&1 &
  PIDS+=($!)
done

STATUS=0
for pid in "${PIDS[@]}"; do
  wait "$pid" || STATUS=1
done
grep -h "pairing verification" "$WORK"/rank*.log || true
if [ "$STATUS" -ne 0 ]; then
  echo "nonlocal_sha256: FAILED — logs:"
  tail -n 20 "$WORK"/rank*.log
  echo "nonlocal_sha256: FAILED"
else
  echo "nonlocal_sha256: OK"
fi
exit $STATUS
