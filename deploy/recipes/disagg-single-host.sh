#!/usr/bin/env bash
# Config #3: disaggregated prefill/decode on one host (xPyD; the reference's
# disagg-single-node recipe shape: recipes/llama-3-70b/vllm/disagg-single-node).
# Usage: MODEL_DIR=... PREFILL=1 DECODE=1 ./disagg-single-host.sh
#
# One process per chip, as in kv-routed-replicas.sh: worker k of the
# PREFILL+DECODE workers is started with only chip k visible.
set -euo pipefail
MODEL_DIR="${MODEL_DIR:?set MODEL_DIR}"
PREFILL="${PREFILL:-1}"
DECODE="${DECODE:-1}"
STORE="${STORE:-127.0.0.1:4222}"
export DYNTPU_STORE_ADDR="$STORE"

python -m dynamo_tpu.runtime.store --host 0.0.0.0 --port "${STORE##*:}" &
sleep 1
chip=0
for i in $(seq 1 "$PREFILL"); do
  env $(python -m dynamo_tpu.utils.device_env "$chip") \
    python -m dynamo_tpu.worker --weights "$MODEL_DIR" --mesh 1,1 \
      --disagg-mode prefill &
  chip=$((chip + 1))
done
for i in $(seq 1 "$DECODE"); do
  env $(python -m dynamo_tpu.utils.device_env "$chip") \
    python -m dynamo_tpu.worker --weights "$MODEL_DIR" --mesh 1,1 \
      --disagg-mode decode --min-remote-prefill-tokens 64 \
      --kvbm-host-blocks 4096 &
  chip=$((chip + 1))
done
python -m dynamo_tpu.frontend --port 8000 --router-mode kv &
wait
