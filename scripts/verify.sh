#!/usr/bin/env bash
# Repo verification: the ROADMAP.md tier-1 line, plus fast targeted modes
# for quick iteration on individual subsystems.
#
#   scripts/verify.sh             # full tier-1 suite (what CI gates on)
#   scripts/verify.sh tracing     # just the -m tracing suite (seconds)
#   scripts/verify.sh resilience  # fault-injection + chaos suites
#   scripts/verify.sh chaos       # seeded chaos sweep; echoes the repro
#                                 # seed (DYNTPU_CHAOS_SEED=<n>) on failure
#   scripts/verify.sh spec        # speculative-decoding parity + accounting
#   scripts/verify.sh kernel      # ragged paged-attention interpret-mode
#                                 # parity suite (CPU, no TPU needed)
#   scripts/verify.sh planner     # closed-loop planner suite incl. the
#                                 # 100+-worker sim sweep; echoes the repro
#                                 # seed (DYNTPU_PLANNER_SEED=<n>) on failure
#   scripts/verify.sh lint        # dynalint static analysis (--check) +
#                                 # analyzer unit tests; echoes the repro
#                                 # line on failure
#   scripts/verify.sh obs         # engine flight recorder suite (stepstats
#                                 # invariants, compile watchdog, /debug/
#                                 # profile smoke, report golden)
#   scripts/verify.sh disagg      # disaggregated KV handoff fault-model
#                                 # suite: epoch guard, wire integrity,
#                                 # chaos storms; echoes the repro seed
#                                 # (DYNTPU_CHAOS_SEED=<n>) on failure
#   scripts/verify.sh tune        # the attention kernel's parity gate
#                                 # (engine/attention_parity.py: every
#                                 # tile of its grid bit-exact on CPU, in
#                                 # a fusion-disabled subprocess)
#   scripts/verify.sh mesh        # SpecLayout sharding parity: 1x8 / 2x4 /
#                                 # 2x2x2 CPU meshes byte-identical to
#                                 # single-device across decode, chunked
#                                 # prefill, spec decode; sharded weights
#                                 # streaming + orbax sharded restore
#   scripts/verify.sh preempt     # preemption-tolerance suite: maintenance
#                                 # -notice evacuation parity, stall
#                                 # watchdog, pressure ladder, chaos storms;
#                                 # echoes the repro seed
#                                 # (DYNTPU_CHAOS_SEED=<n>) on failure
#   scripts/verify.sh quant       # quantized serving suite: int8/fp8 weight
#                                 # + KV quantization (bf16 byte-parity,
#                                 # per-dtype logprob budgets, kernel parity
#                                 # with NaN trash blocks, kvbm/disagg
#                                 # round-trips); echoes the repro line on
#                                 # failure
#   scripts/verify.sh replay      # trace-replay scoreboard suite: seeded
#                                 # multi-tenant replay vs a real-engine
#                                 # cluster, cross-checked against recorder
#                                 # + spans; echoes the repro seed
#                                 # (DYNTPU_REPLAY_SEED=<n>) on failure
#   scripts/verify.sh chaosreplay # chaos-replay gauntlet: seeded fault
#                                 # waves (store flap + relay truncation +
#                                 # stall + preemption) replayed with
#                                 # attributed-recovery scoring; echoes the
#                                 # repro seed (DYNTPU_REPLAY_SEED=<n>,
#                                 # same knob as CHAOS_SEED) on failure
#   scripts/verify.sh prefix      # global prefix cache suite: radix-tree
#                                 # invariants, byte parity cache-on vs
#                                 # cache-off, tiered demote/onboard,
#                                 # prefix-aware routing, replay
#                                 # prefix_vs_index; echoes the repro seed
#                                 # (DYNTPU_PREFIX_SEED=<n>) on failure
set -u

cd "$(dirname "$0")/.."

if [ "${1:-}" = "tracing" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m tracing \
        -p no:cacheprovider
fi

if [ "${1:-}" = "spec" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m spec \
        -p no:cacheprovider
fi

if [ "${1:-}" = "kernel" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m kernel \
        -p no:cacheprovider
fi

if [ "${1:-}" = "tune" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m tune \
        -p no:cacheprovider
fi

if [ "${1:-}" = "mesh" ]; then
    rc=0
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m mesh \
        -p no:cacheprovider || rc=$?
    if [ "$rc" -ne 0 ]; then
        echo "mesh parity FAILED; reproduce with:"
        echo "  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\"
        echo "    JAX_PLATFORMS=cpu python -m pytest tests/ -m mesh"
    fi
    exit $rc
fi

if [ "${1:-}" = "quant" ]; then
    rc=0
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m quant \
        -p no:cacheprovider || rc=$?
    if [ "$rc" -ne 0 ]; then
        echo "quantized serving suite FAILED; reproduce with:"
        echo "  JAX_PLATFORMS=cpu python -m pytest tests/test_quantized.py -m quant"
    fi
    exit $rc
fi

if [ "${1:-}" = "obs" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m observability \
        -p no:cacheprovider
fi

if [ "${1:-}" = "lint" ]; then
    rc=0
    env JAX_PLATFORMS=cpu python -m dynamo_tpu.analysis --check || rc=$?
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m analysis \
        -p no:cacheprovider || rc=$?
    if [ "$rc" -ne 0 ]; then
        echo "dynalint FAILED; reproduce with:"
        echo "  python -m dynamo_tpu.analysis --check"
        echo "fix the finding, add '# dynalint: disable=DTxxx' with a reason,"
        echo "or (grandfathering only) python -m dynamo_tpu.analysis --update-baseline"
    fi
    exit $rc
fi

if [ "${1:-}" = "resilience" ]; then
    exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
        -m 'resilience or chaos' -p no:cacheprovider
fi

if [ "${1:-}" = "planner" ]; then
    set -o pipefail
    rm -f /tmp/_planner.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m planner \
        -p no:cacheprovider 2>&1 | tee /tmp/_planner.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every planner test prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'PLANNER_SEED=[0-9]+' /tmp/_planner.log | sort -u | tr '\n' ' ')
        echo "planner sweep FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh planner"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "chaos" ]; then
    set -o pipefail
    rm -f /tmp/_chaos.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos \
        -p no:cacheprovider 2>&1 | tee /tmp/_chaos.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every chaos test prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'CHAOS_SEED=[0-9]+' /tmp/_chaos.log | sort -u | tr '\n' ' ')
        echo "chaos sweep FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh chaos"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "disagg" ]; then
    set -o pipefail
    rm -f /tmp/_disagg.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m disagg \
        -p no:cacheprovider 2>&1 | tee /tmp/_disagg.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every disagg chaos test prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'CHAOS_SEED=[0-9]+' /tmp/_disagg.log | sort -u | tr '\n' ' ')
        echo "disagg suite FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh disagg"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "preempt" ]; then
    set -o pipefail
    rm -f /tmp/_preempt.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m preempt \
        -p no:cacheprovider 2>&1 | tee /tmp/_preempt.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every preemption storm prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'CHAOS_SEED=[0-9]+' /tmp/_preempt.log | sort -u | tr '\n' ' ')
        echo "preemption suite FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh preempt"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "replay" ]; then
    set -o pipefail
    rm -f /tmp/_replay.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m replay \
        -p no:cacheprovider 2>&1 | tee /tmp/_replay.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every replay test prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'REPLAY_SEED=[0-9]+' /tmp/_replay.log | sort -u | tr '\n' ' ')
        echo "trace-replay suite FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh replay"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "prefix" ]; then
    set -o pipefail
    rm -f /tmp/_prefix.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m prefix \
        -p no:cacheprovider 2>&1 | tee /tmp/_prefix.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every seeded prefix test prints its seed; surface a one-line repro
        seeds=$(grep -aoE 'PREFIX_SEED=[0-9]+' /tmp/_prefix.log | sort -u | tr '\n' ' ')
        echo "prefix cache suite FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh prefix"
        done
    fi
    exit $rc
fi

if [ "${1:-}" = "chaosreplay" ]; then
    set -o pipefail
    rm -f /tmp/_chaosreplay.log
    env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaosreplay \
        -p no:cacheprovider 2>&1 | tee /tmp/_chaosreplay.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
        # every gauntlet run prints CHAOS_SEED (alias of REPLAY_SEED);
        # surface a one-line repro
        seeds=$(grep -aoE 'CHAOS_SEED=[0-9]+' /tmp/_chaosreplay.log | sed 's/CHAOS/REPLAY/' | sort -u | tr '\n' ' ')
        echo "chaos-replay gauntlet FAILED; reproduce with e.g.:"
        for s in $seeds; do
            echo "  DYNTPU_${s} scripts/verify.sh chaosreplay"
        done
    fi
    exit $rc
fi

# Tier-1 (ROADMAP.md): full suite minus slow markers, with a parseable
# passed-dot count even when collection partially errors.
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
