#!/usr/bin/env bash
# Tier-1 verify entrypoint (the exact command from ROADMAP.md).
#
# Usage: scripts/ci.sh [extra pytest args]
#        scripts/ci.sh --participation-smoke
#                                      # fault-injection sweep: dropout x
#                                      # staleness across fedgalore vs the
#                                      # fedavg-LoRA baseline; writes
#                                      # BENCH_participation.json and gates
#                                      # on its acceptance keys
#        scripts/ci.sh --robust-smoke  # adversary sweep: NaN/scale attacks
#                                      # vs quarantine + robust factored
#                                      # aggregation, engine AND runtime
#                                      # (with a coverage floor on the
#                                      # population adversary layer when
#                                      # pytest-cov is installed); writes
#                                      # BENCH_robust.json and gates on
#                                      # honest bit-identity (both drivers),
#                                      # NaN containment, bounded attack
#                                      # degradation and hetero-basis
#                                      # attack parity
#        scripts/ci.sh --sync-smoke    # batched-bucket 𝒮 + pipelined-scan
#                                      # leg: runs the sync parity suites
#                                      # (with a coverage floor on
#                                      # state_sync/ajive when pytest-cov is
#                                      # installed)
#        scripts/ci.sh --serve-smoke   # multi-tenant serving leg: runs the
#                                      # serving suite (batched hetero-adapter
#                                      # kernel, scan≡eager decode parity,
#                                      # SlotServer continuous batching; with
#                                      # a coverage floor on launch/serve +
#                                      # launch/adapters when pytest-cov is
#                                      # installed), then runs bench_serve
#                                      # --smoke and gates decode parity,
#                                      # scan ≥ eager throughput, hetero-batch
#                                      # ≥ 0.8x single-adapter tokens/s, and
#                                      # continuous-batching parity on
#                                      # BENCH_serve.json
# Dev-only deps (pytest, hypothesis, pytest-cov) are listed in
# requirements-dev.txt; tests that need hypothesis self-skip when it is
# absent, and the --sync-smoke coverage floor downgrades to plain pytest
# without pytest-cov.
set -euo pipefail
cd "$(dirname "$0")/.."

# --- environment hygiene (mirrors benchmarks/run.py:_env_hygiene) ------------
# tcmalloc, when the image ships it: glibc malloc fragments badly under the
# round's large donated-buffer churn; the report threshold silences tcmalloc's
# per-allocation warnings for the multi-GB cohort buffers.
if [[ -z "${LD_PRELOAD:-}" ]]; then
    for _tc in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
               /usr/lib/libtcmalloc.so.4; do
        [[ -f "$_tc" ]] && export LD_PRELOAD="$_tc" && break
    done
fi
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
# Absl/TF C++ banner noise off by default (keeps pytest/bench output greppable).
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
# REPRO_HOST_DEVICES=N fakes an N-device host platform (multi-device mesh
# tests and sharded smoke runs on CPU-only hosts).
if [[ -n "${REPRO_HOST_DEVICES:-}" ]]; then
    export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${REPRO_HOST_DEVICES}"
fi
# REPRO_STEP_MARKERS=1 adds per-step trace markers for profiles. Opt-in only:
# the flag is rejected by CPU builds of XLA ("Unknown flags in XLA_FLAGS").
if [[ "${REPRO_STEP_MARKERS:-0}" == "1" ]]; then
    export XLA_FLAGS="${XLA_FLAGS:-} --xla_step_marker_location=1"
fi

if [[ "${1:-}" == "--sync-smoke" ]]; then
    shift
    # Sync parity subset: bucketed 𝒮 ≡ per-leaf, pipelined ≡ sequential,
    # batched-eigh kernel vs LAPACK. pytest-cov (when installed) enforces a
    # line-coverage floor on the two modules this suite locks in.
    COV_ARGS=()
    if PYTHONPATH=src python -c "import pytest_cov" 2>/dev/null; then
        COV_ARGS=(--cov=repro.core.state_sync --cov=repro.core.ajive
                  --cov-report=term --cov-fail-under=80)
    else
        echo "pytest-cov not installed — sync parity runs without the" \
             "coverage floor"
    fi
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
        ${COV_ARGS[@]+"${COV_ARGS[@]}"} \
        tests/test_sync_batched.py tests/test_batched_eigh.py
    exit 0
fi

if [[ "${1:-}" == "--participation-smoke" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m \
        benchmarks.bench_participation --smoke \
        --out BENCH_participation.json "$@"
    python - <<'EOF'
import json
acc = json.load(open("BENCH_participation.json"))["acceptance"]
print("participation acceptance:", json.dumps(acc, indent=1))
# Robustness gates: the masked fused round must be bit-identical to the
# unmasked round under full participation, drift must stay bounded through
# the stale-merge path, and fedgalore must degrade no worse than the
# fedavg-LoRA baseline across the dropout x staleness fault grid.
assert acc["masked_round_parity"], "full-participation mask != unmasked round"
assert acc["stale_drift_bounded"], (
    f"stale aggregation error unbounded: {acc['max_stale_weight_err']:.4f}")
assert acc["fedgalore_degradation_ok"], (
    f"fedgalore degrades more than baseline under faults: "
    f"{acc['fedgalore_worst_degradation']:.4f} vs "
    f"{acc['baseline_worst_degradation']:.4f} (+tol)")
EOF
    exit 0
fi

if [[ "${1:-}" == "--robust-smoke" ]]; then
    shift
    # Robustness suite first: operator/property invariants + the guarded
    # engine/runtime rounds, with a line-coverage floor on the population
    # adversary layer (cohort plans, corruption schedules) when pytest-cov
    # is installed.
    COV_ARGS=()
    if PYTHONPATH=src python -c "import pytest_cov" 2>/dev/null; then
        COV_ARGS=(--cov=repro.core.population
                  --cov-report=term --cov-fail-under=70)
    else
        echo "pytest-cov not installed — robust suite runs without the" \
             "coverage floor"
    fi
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
        ${COV_ARGS[@]+"${COV_ARGS[@]}"} \
        tests/test_robust.py tests/test_robust_properties.py
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m \
        benchmarks.bench_robust --smoke --out BENCH_robust.json "$@"
    python - <<'EOF'
import json
acc = json.load(open("BENCH_robust.json"))["acceptance"]
print("robust acceptance:", json.dumps(acc, indent=1))
# Defense-in-depth gates: the all-honest guarded round must be bit-identical
# to the unguarded round (engine AND sharded runtime), every NaN-adversary
# run under a defense must stay finite end-to-end, for each attack the best
# defended cell must stay within the degradation bound while the undefended
# cell degrades strictly more (or diverges), and the hetero-basis
# (svd-refresh) defended runs must track their shared-basis twins.
assert acc["attacks_landed"], "adversary plans drew zero corrupted clients"
assert acc["honest_bit_identity"], "honest guarded round != unguarded round"
assert acc["nan_quarantined"], "NaN adversary leaked past the quarantine"
assert acc["attack_degradation_bounded"], (
    f"attack degradation unbounded: {json.dumps(acc['degradation'])}")
assert acc["runtime_attacks_landed"], "runtime schedule drew zero attacks"
assert acc["runtime_honest_bit_identity"], (
    "honest guarded runtime round != unguarded runtime round")
assert acc["hetero_attack_parity"], (
    "hetero-basis defended runs diverged from shared-basis twins: "
    f"{json.dumps(acc['hetero_parity_rel'])} vs bound "
    f"{acc['hetero_bound']}")
EOF
    exit 0
fi

if [[ "${1:-}" == "--serve-smoke" ]]; then
    shift
    # Serving suite first: batched hetero-adapter kernel vs per-request
    # reference, scan≡eager bit-identity, adapter-store spill round-trips,
    # SlotServer churn parity — with a line-coverage floor on the serving
    # layer when pytest-cov is installed.
    COV_ARGS=()
    if PYTHONPATH=src python -c "import pytest_cov" 2>/dev/null; then
        COV_ARGS=(--cov=repro.launch.serve --cov=repro.launch.adapters
                  --cov-report=term --cov-fail-under=80)
    else
        echo "pytest-cov not installed — serving suite runs without the" \
             "coverage floor"
    fi
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q \
        ${COV_ARGS[@]+"${COV_ARGS[@]}"} \
        tests/test_serve.py
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m \
        benchmarks.bench_serve --smoke --out BENCH_serve.json "$@"
    python - <<'EOF'
import json
acc = json.load(open("BENCH_serve.json"))["acceptance"]
print("serve acceptance:", json.dumps(acc, indent=1))
# Serving gates: the fused scan decode must emit the exact greedy tokens of
# the eager loop and be no slower, a heterogeneous-adapter batch (every row
# its own factor pair over one shared base GEMM) must hold >= 0.8x the
# single-adapter throughput, and continuous batching must reproduce straight
# generation per request through retire/admit churn.
assert acc["decode_parity"], "scan decode != eager greedy tokens"
assert acc["scan_speedup_b4_n64"] >= 1.0, (
    f"fused scan decode slower than eager loop: "
    f"x{acc['scan_speedup_b4_n64']:.2f}")
assert acc["hetero_tput_ratio_g16_b8"] >= 0.8, (
    f"hetero-adapter batch below 0.8x single-adapter throughput at "
    f"G={acc['hetero_gate_adapters']}: x{acc['hetero_tput_ratio_g16_b8']:.2f}")
assert acc["continuous_parity"], (
    "SlotServer continuous batching != straight generate per request")
EOF
    exit 0
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
