#!/usr/bin/env bash
# The reference recipe load shape, runnable against any dynamo-tpu frontend
# (recipes/llama-3-70b/vllm/disagg-single-node/perf.yaml:41-50: ISL 8192
# sigma=0, OSL 1024, concurrency 64, 320 requests, streaming).
#
#   URL=http://127.0.0.1:8000 MODEL=llama70b ./perf-baseline.sh
set -euo pipefail
URL="${URL:-http://127.0.0.1:8000}"
MODEL="${MODEL:?set MODEL to the served model name}"

exec python -m benchmarks.loadgen \
    --url "$URL" --model "$MODEL" \
    --isl 8192 --osl 1024 --concurrency 64 --requests 320
