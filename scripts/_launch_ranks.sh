# Shared rank-spawning harness for the per-kernel launcher matrix
# (dfft_test.sh, dmsm_bench.sh, dpp_test.sh, million.sh) — the role the
# reference's scripts/{dfft_test,dmsm_bench,dpp_test,million}.zsh share:
# generate certs + address file, spawn N ranks of a given example,
# wait for all, propagate any failure. Sourced, not executed.
#
# These launchers are CPU-backend tests of the mTLS star transport: N OS
# processes cannot share one accelerator (a chip belongs to one process),
# so every rank is pinned to XLA:CPU. The chip path is chip_smoke.py.
#
# Caller sets: EXAMPLE (python file), EXTRA_ARGS (array, per-rank args
# appended after --id/--input/--certs/--n). Honors N, PORT, PLAIN,
# WORK_DIR like nonlocal_sha256.sh, plus ROUND_RETRIES
# (default 1): a failed round — any rank exiting non-zero, e.g. on a
# transient MpcNetError — relaunches ALL ranks up to that many extra
# times before the harness reports failure.

set -euo pipefail

N=${N:-8}
PORT=${PORT:-9805}
WORK=${WORK_DIR:-$(mktemp -d)}
if [ -z "${WORK_DIR:-}" ]; then trap 'rm -rf "$WORK"' EXIT; fi

TLS_ARGS=()
if [ "${PLAIN:-0}" = "1" ]; then
  TLS_ARGS+=(--plain)
else
  for i in $(seq 0 $((N - 1))); do
    python -m distributed_groth16_tpu.utils.certs "$i" "$WORK/certs" >/dev/null
  done
fi

ADDR="$WORK/addresses"
: > "$ADDR"
for i in $(seq 0 $((N - 1))); do
  echo "127.0.0.1:$((PORT + i))" >> "$ADDR"
done

ROUND_RETRIES=${ROUND_RETRIES:-1}
ATTEMPT=0
while :; do
  PIDS=()
  for i in $(seq $((N - 1)) -1 0); do
    JAX_PLATFORMS=cpu python "$EXAMPLE" \
      --id "$i" --input "$ADDR" --certs "$WORK/certs" --n "$N" \
      "${EXTRA_ARGS[@]}" "${TLS_ARGS[@]}" \
      > "$WORK/rank$i.log" 2>&1 &
    PIDS+=($!)
  done

  STATUS=0
  for pid in "${PIDS[@]}"; do
    wait "$pid" || STATUS=1
  done
  if [ "$STATUS" -eq 0 ] || [ "$ATTEMPT" -ge "$ROUND_RETRIES" ]; then
    break
  fi
  ATTEMPT=$((ATTEMPT + 1))
  echo "$(basename "$EXAMPLE"): round failed; retry $ATTEMPT/$ROUND_RETRIES"
done

grep -h "rank 0:" "$WORK"/rank*.log || true
if [ "$STATUS" -ne 0 ]; then
  echo "$(basename "$EXAMPLE"): FAILED after $((ATTEMPT + 1)) attempt(s) — logs:"
  tail -n 20 "$WORK"/rank*.log
  exit 1
fi
