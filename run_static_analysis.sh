#!/usr/bin/env bash
# Static-analysis gate for ray_tpu (ARCHITECTURE.md "Static analysis &
# concurrency invariants"). Four stages, all must pass:
#
#   0. self-check — raylint lints its own engine (ray_tpu/devtools/), the
#      shipped fixture corpus round-trips expected.json exactly, and the
#      machine-readable `--rules` listing is cross-checked against this
#      header and the ARCHITECTURE.md rule table so neither can drift.
#   1. raylint — the framework-aware AST linter (R1..R29, including the
#      whole-program call-graph rules, the path-sensitive dataflow
#      rules, the cross-process stitched-graph rules, the
#      field-level thread-safety rules R23-R25, and the static SPMD
#      sharding rules R27-R29) over
#      ray_tpu/, bench.py, bench_micro.py, and tests/; any
#      non-allowlisted finding fails the gate. tests/ runs under a
#      scoped allow profile (see below). Emits a SARIF 2.1.0 artifact
#      and the R29 collective-cost plan (comms_manifest.json, the
#      input to `ray-tpu doctor --comms-baseline`'s __manifest__ gate)
#      next to the JSON summary, reports the incremental-cache hit rate
#      in the timing summary, and warns when the stage outruns its
#      recorded cold-cache baseline by >50%.
#   2. lockwatch — the tier-1 test suite once under RAY_TPU_LOCKWATCH=1;
#      every process summary line must report zero lock-order cycles.
#      Static R11 findings and these runtime reports share one cycle
#      format, so a cycle seen here should have a matching R11 site list.
#   3. gcc -fanalyzer — syntax-only analyzer pass over the four
#      _native/*.cc translation units (protobuf-dependent ones are
#      skipped with a notice when protoc is unavailable to generate
#      raytpu.pb.h).
#
#   ./run_static_analysis.sh              # all four stages
#   SKIP_LOCKWATCH_TESTS=1 ./run_static_analysis.sh   # skip stage 2
set -uo pipefail
cd "$(dirname "$0")"

fail=0
declare -a STAGE_TIMES=()

stage_done() {  # stage_done <label> <t0> <status>
  local el=$(( SECONDS - $2 ))
  STAGE_TIMES+=("$1: $3 in ${el}s")
  echo "-- $1: $3 (${el}s)"
}

echo "== [stage 0] raylint self-check =="
t0=$SECONDS
st=OK
# (a) the analyzer must be clean under its own rules
if ! python -m ray_tpu.devtools.lint ray_tpu/devtools; then
  st=FAIL; fail=1
fi
# (b) the fixture corpus must round-trip expected.json exactly
if ! python -m ray_tpu.devtools.lint --self-check; then
  st=FAIL; fail=1
fi
# (c) docs drift: the registry is the source of truth for "R1..RN" above
# and for the ARCHITECTURE.md rule table
if ! python - <<'EOF'
import json, re, subprocess, sys
listing = json.loads(subprocess.run(
    [sys.executable, "-m", "ray_tpu.devtools.lint", "--rules"],
    capture_output=True, text=True, check=True).stdout)
ids = [r["id"] for r in listing]
rmax = max(int(i[1:]) for i in ids)
header = open("run_static_analysis.sh", encoding="utf-8").read()
if f"R1..R{rmax}" not in header:
    print(f"drift: run_static_analysis.sh header does not say R1..R{rmax}")
    sys.exit(1)
arch = open("ARCHITECTURE.md", encoding="utf-8").read()
missing = [i for i in ids
           if not re.search(rf"\*\*{i}\b", arch)]
if missing:
    print(f"drift: ARCHITECTURE.md rule table is missing {missing}")
    sys.exit(1)
print(f"docs in sync with registry ({len(ids)} rules, R1..R{rmax})")
EOF
then
  st=FAIL; fail=1
fi
stage_done "stage 0 (self-check)" "$t0" "$st"

echo "== [stage 1] raylint (ray_tpu bench.py bench_micro.py chip_smoke.py tests) =="
t0=$SECONDS
st=OK
# tests/ allow profile: test code legitimately pokes checkpoint
# directories (R9), simulates rank-divergent schedules on purpose (R12),
# registers throwaway metrics (R22), hammers shared state from
# deliberately-racing helper threads (R23-R25), and pins autopilot-owned
# knobs to build deterministic scenarios (R26); scoped here so
# production code can never ride on it.
LINT_JSON="$(mktemp /tmp/raytpu_lint.XXXXXX.json)"
LINT_ERR="$(mktemp /tmp/raytpu_lint.XXXXXX.err)"
# CI artifact: SARIF 2.1.0 log of every finding (empty `results` on a
# clean tree), for editor/code-scanning ingestion
LINT_SARIF="${RAYLINT_SARIF_OUT:-/tmp/raytpu_lint.sarif.json}"
# CI artifact: the static collective plan R29 derives from the sharding
# model — ships next to the SARIF log and feeds the runtime
# manifest-vs-ledger cross-check (doctor --comms-baseline __manifest__,
# run_sanitizers.sh).
LINT_MANIFEST="${RAYLINT_MANIFEST_OUT:-/tmp/raytpu_comms_manifest.json}"
if python -m ray_tpu.devtools.lint ray_tpu bench.py bench_micro.py chip_smoke.py tests \
     --allow-in "tests/:R9,R12,R22,R23,R24,R25,R26" --json --sarif "$LINT_SARIF" \
     --comms-manifest "$LINT_MANIFEST" \
     > "$LINT_JSON" 2> "$LINT_ERR"; then
  python - "$LINT_JSON" <<'EOF'
import json, sys
rows = json.load(open(sys.argv[1]))
print(f"raylint: {len(rows)} finding(s) across the widened file set")
EOF
else
  st=FAIL; fail=1
  python - "$LINT_JSON" <<'EOF'
import collections, json, sys
rows = json.load(open(sys.argv[1]))
per = collections.Counter(r["rule"] for r in rows)
summary = ", ".join(f"{k}: {v}" for k, v in sorted(per.items()))
print(f"raylint: {len(rows)} finding(s) ({summary})", file=sys.stderr)
for r in rows:
    print(f"{r['path']}:{r['line']}: {r['rule']}({r['tag']}): "
          f"{r['message']}", file=sys.stderr)
EOF
fi
cat "$LINT_ERR" >&2
CACHE_LINE="$(grep -o 'raylint-cache: .*' "$LINT_ERR" | tail -1)"
# Per-rule wall time for the project rules (plus the shared graph
# build), straight from the engine — the first place to look when the
# stage-1 budget check below trips.
TIMES_LINE="$(grep -o 'raylint-times: .*' "$LINT_ERR" | tail -1)"
rm -f "$LINT_JSON" "$LINT_ERR"
stage_done "stage 1 (raylint)" "$t0" "$st"
STAGE_TIMES+=("stage 1 cache: ${CACHE_LINE#raylint-cache: }")
STAGE_TIMES+=("stage 1 rule times: ${TIMES_LINE#raylint-times: }")
# Budget check against the recorded cold-cache baseline (full R1..R29
# run over the widened file set, incl. the stitch pass, the R23-R25
# field plan, and the R27-R29 sharding model, 2026-08): a >50%
# overshoot means a rule regressed into super-linear work or the cache
# stopped landing.
STAGE1_BASELINE_S="${RAYLINT_STAGE1_BASELINE_S:-45}"
st1_el=$(( SECONDS - t0 ))
if [ "$st1_el" -gt $(( STAGE1_BASELINE_S * 3 / 2 )) ]; then
  echo "WARNING: stage 1 took ${st1_el}s, >50% over its recorded" \
       "baseline of ${STAGE1_BASELINE_S}s — check rule cost or cache" >&2
  STAGE_TIMES+=("stage 1 budget: OVER (${st1_el}s vs ${STAGE1_BASELINE_S}s baseline)")
fi

echo "== [stage 2] lockwatch (tier-1 under RAY_TPU_LOCKWATCH=1) =="
t0=$SECONDS
st=OK
if [ "${SKIP_LOCKWATCH_TESTS:-0}" = "1" ]; then
  st=SKIPPED
  echo "skipped (SKIP_LOCKWATCH_TESTS=1)"
else
  LW_LOG="$(mktemp /tmp/raytpu_lockwatch.XXXXXX.log)"
  RAY_TPU_LOCKWATCH=1 JAX_PLATFORMS=cpu \
    timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
      --continue-on-collection-errors -p no:cacheprovider \
      -p no:xdist -p no:randomly 2>&1 | tee "$LW_LOG" | tail -5
  # Every LOCKWATCH summary line (one per process that created locks)
  # must report zero cycles; the suite's own pass/fail is tier-1's job.
  if grep -a "^LOCKWATCH: " "$LW_LOG" | grep -av ", 0 cycles," | grep -aq .; then
    echo "FAIL: lock-order cycles observed:" >&2
    grep -a "^LOCKWATCH" "$LW_LOG" | grep -av ", 0 cycles," >&2
    st=FAIL; fail=1
  elif ! grep -aq "^LOCKWATCH: " "$LW_LOG"; then
    echo "FAIL: no LOCKWATCH summary seen — watchdog did not install" >&2
    st=FAIL; fail=1
  else
    echo "lockwatch: zero cycles across $(grep -ac '^LOCKWATCH: ' "$LW_LOG") process summaries"
  fi
fi
stage_done "stage 2 (lockwatch)" "$t0" "$st"

echo "== [stage 3] gcc -fanalyzer over _native/*.cc =="
t0=$SECONDS
st=OK
GEN_DIR="ray_tpu/_native/gen"
if command -v protoc >/dev/null 2>&1; then
  mkdir -p "$GEN_DIR"
  protoc --proto_path=ray_tpu/protocol --cpp_out="$GEN_DIR" \
    ray_tpu/protocol/raytpu.proto
fi
PY_INC="$(python3-config --includes)"
for src in ray_tpu/_native/cpp_worker.cc ray_tpu/_native/object_store.cc \
           ray_tpu/_native/scheduling.cc ray_tpu/_native/state_service.cc; do
  # the protobuf-linked units need the generated header
  if grep -q 'raytpu\.pb\.h' "$src" && [ ! -f "$GEN_DIR/raytpu.pb.h" ]; then
    echo "skip $src (no protoc to generate raytpu.pb.h)"
    continue
  fi
  echo "-- $src"
  # shellcheck disable=SC2086
  if ! g++ -fanalyzer -fsyntax-only -std=c++17 $PY_INC \
        -I "$GEN_DIR" -I ray_tpu/_native "$src"; then
    st=FAIL; fail=1
  fi
done
stage_done "stage 3 (gcc -fanalyzer)" "$t0" "$st"

echo "== stage timings =="
for line in "${STAGE_TIMES[@]}"; do
  echo "  $line"
done

if [ "$fail" -ne 0 ]; then
  echo "static analysis: FAIL" >&2
  exit 1
fi
echo "static analysis: OK"
