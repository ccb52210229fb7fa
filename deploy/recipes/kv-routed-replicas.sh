#!/usr/bin/env bash
# Config #2: N replicated one-chip workers behind the KV-cache-aware router.
# Usage: MODEL_DIR=... REPLICAS=4 ./kv-routed-replicas.sh
#
# One process per chip: a TPU chip belongs to one process, and a process
# opens every chip it can see — so each worker is started with exactly one
# chip visible (dynamo_tpu.utils.device_env.one_chip_env, set in the child's
# environment before JAX is imported). Established by
# `chip_smoke.py --four-chips` on a v5e 2x2 host; only one chip per worker
# has been established this way, so a (1,N)-mesh worker is not offered here:
# it takes the whole host and is started alone (agg-single-host.sh).
set -euo pipefail
MODEL_DIR="${MODEL_DIR:?set MODEL_DIR}"
REPLICAS="${REPLICAS:-2}"
STORE="${STORE:-127.0.0.1:4222}"
export DYNTPU_STORE_ADDR="$STORE"

python -m dynamo_tpu.runtime.store --host 0.0.0.0 --port "${STORE##*:}" &
sleep 1
for i in $(seq 0 $((REPLICAS - 1))); do
  env $(python -m dynamo_tpu.utils.device_env "$i") \
    python -m dynamo_tpu.worker --weights "$MODEL_DIR" --mesh 1,1 &
done
python -m dynamo_tpu.frontend --port 8000 --router-mode kv \
    --busy-threshold 0.95 &
wait
