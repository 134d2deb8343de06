#!/usr/bin/env bash
# Local multi-worker DiLoCo launcher (reference: open_diloco/run_training.sh).
#
# Usage: ./scripts/run_training.sh <num_workers> <initial_peer|auto> [extra train flags...]
#
#   num_workers   number of DiLoCo workers to spawn on this machine
#   initial_peer  rendezvous address host:port, or "auto" to start an
#                 in-process rendezvous daemon on port 29400
#   extra flags   forwarded verbatim to `python -m opendiloco_tpu.train`
#
# Example (8-worker llama-150m, 500 local steps — README.md:131-148 recipe):
#   ./scripts/run_training.sh 8 auto --path-model 150m \
#       --total-batch-size 512 --per-device-train-batch-size 32 \
#       --diloco.local-steps 500 --project my-run

set -euo pipefail

if [ "$#" -lt 2 ]; then
  echo "usage: $0 <num_workers> <initial_peer|auto> [train flags...]" >&2
  exit 1
fi

NUM_WORKERS=$1
INITIAL_PEER=$2
shift 2

REPO_DIR="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO_DIR${PYTHONPATH:+:$PYTHONPATH}"

# A chip belongs to one process at a time, and a worker builds its mesh from
# every device it can see. So on a TPU host worker i is shown chip i and no
# other, and more workers than chips is refused (elsewhere, e.g. under
# JAX_PLATFORMS=cpu, workers share the host as before). The probe is a
# process of its own: it has let go of the chips before any worker starts.
read -r PLATFORM NUM_DEVICES < <(
  python -c 'import jax; d = jax.devices(); print(d[0].platform, len(d))'
) || true
if [ -z "${PLATFORM:-}" ]; then
  echo "$0: could not ask JAX for its devices" >&2
  exit 1
fi
if [ "$PLATFORM" = "tpu" ] && [ "$NUM_WORKERS" -gt "$NUM_DEVICES" ]; then
  echo "$0: $NUM_WORKERS workers but $NUM_DEVICES TPU chips on this host:" \
    "a chip belongs to one process, so each worker needs its own" >&2
  exit 1
fi

RDV_PID=""
if [ "$INITIAL_PEER" = "auto" ]; then
  INITIAL_PEER="127.0.0.1:29400"
  # prefer the native daemon when built (make -C native)
  if [ -x "$REPO_DIR/native/odtp-rendezvousd" ]; then
    "$REPO_DIR/native/odtp-rendezvousd" --port 29400 \
      --identity-file "$REPO_DIR/.rendezvous_identity" &
  else
    python -m opendiloco_tpu.diloco.rendezvous --host 127.0.0.1 --port 29400 \
      --identity-file "$REPO_DIR/.rendezvous_identity" &
  fi
  RDV_PID=$!
  trap '[ -n "$RDV_PID" ] && kill $RDV_PID 2>/dev/null || true' EXIT
  sleep 1
fi

PIDS=()
for RANK in $(seq 0 $((NUM_WORKERS - 1))); do
  # secondary workers keep wandb quiet (reference run_training.sh:69)
  if [ "$RANK" -ne 0 ]; then export WANDB_MODE=${WANDB_MODE:-disabled}; fi
  CHIP_ENV=()
  if [ "$PLATFORM" = "tpu" ]; then
    # one-chip process on a multi-chip host (libtpu's own variables, in
    # both of their spellings): see only chip RANK, a 1x1x1 topology, and
    # a controller port of its own
    CHIP_ENV=(
      TPU_VISIBLE_CHIPS="$RANK"
      TPU_VISIBLE_DEVICES="$RANK"
      TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1
      TPU_CHIPS_PER_HOST_BOUNDS=1,1,1
      TPU_PROCESS_BOUNDS=1,1,1
      TPU_HOST_BOUNDS=1,1,1
      TPU_MESH_CONTROLLER_ADDRESS="localhost:$((8476 + RANK))"
      TPU_MESH_CONTROLLER_PORT="$((8476 + RANK))"
    )
  fi
  env "${CHIP_ENV[@]}" python -m opendiloco_tpu.train \
    --diloco.initial-peers "$INITIAL_PEER" \
    --diloco.world-rank "$RANK" \
    --diloco.galaxy-size "$NUM_WORKERS" \
    "$@" &
  PIDS+=($!)
done

STATUS=0
for PID in "${PIDS[@]}"; do
  wait "$PID" || STATUS=$?
done
exit $STATUS
