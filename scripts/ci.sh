#!/usr/bin/env bash
# CI gate: the ROADMAP tier-1 suite plus fast subsets (fused-plan
# equivalence, metrics/flight-recorder, exec overlap/donation golden
# equivalence, ft chaos-golden/resume, serve API/admission) so a
# regression there fails loudly even when only the quick gate runs.
#
#   scripts/ci.sh          # tier-1 + plan/metrics/exec/ft subsets
#                          # + full serve subset (kill-9 queue replay)
#   scripts/ci.sh quick    # plan/metrics/exec/ft/serve fast subsets (~1 min)
#   scripts/ci.sh lint     # mrlint only (all 5 rules, whole package)
#   scripts/ci.sh fleet    # serve-fleet subset only (lease/ring units
#                          # + kill -9 failover goldens + router)
#   scripts/ci.sh dist     # multi-process data plane subset (watchdog/
#                          # heartbeat fakes + slow multi-rank goldens:
#                          # peer_kill shrink-and-resume, peer_hang)
#   scripts/ci.sh obsdist  # fleet observability subset (sync observer/
#                          # federation units + stitched-trace golden,
#                          # straggler attribution, federation chaos)
#   scripts/ci.sh stream   # standing-query subset (tailer/cutter units,
#                          # incremental + kill-9 goldens, stream takeover)
#   scripts/ci.sh cache    # caching-tier subset (CAS/memo units +
#                          # warm-restart/fleet hits, corruption
#                          # fallback, GC intent replay)
set -euo pipefail
cd "$(dirname "$0")/.."

run_plan_subset() {
  echo "== plan equivalence subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_plan.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_metrics_subset() {
  echo "== metrics / flight-recorder subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_metrics.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_exec_subset() {
  echo "== exec overlap/donation equivalence subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_exec.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_ft_subset() {
  echo "== ft chaos-golden / retry / resume subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_ft.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_serve_subset_quick() {
  echo "== serve API round-trip + admission subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q \
      -k 'roundtrip or admission or drain or queue_bounds or plan_cache or rate_limit' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_context_subset() {
  echo "== trace-context / cost-profile / SLO subset (fast) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_context.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

# mrlint (doc/lint.md): trace purity, lock discipline, cache-key
# completeness, knob registry + the metric catalog (rule 5).
# quick: report only files changed vs HEAD/HEAD~1 (analysis still sees
# the whole package, so cross-module rules stay sound); full: whole
# package, findings and counts as JSON in mrlint.json.
run_lint_quick() {
  echo "== mrlint (changed-module scope) =="
  python scripts/mrlint.py --changed
}

run_lint_full() {
  echo "== mrlint (whole package) =="
  python scripts/mrlint.py --json mrlint.json
}

run_megafuse_subset_quick() {
  echo "== megafuse subset (fast): fused-vs-eager goldens + kernel-launch accounting =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_megafuse.py -q \
      -k 'golden or kernel' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_megafuse_subset_full() {
  echo "== megafuse subset (full): dispatch counts, fallbacks, chaos, telemetry =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_megafuse.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_wire_subset_quick() {
  echo "== wire-codec subset (fast): codec round-trip + goldens =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_wire.py -q \
      -k 'codec or golden' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_wire_subset_full() {
  echo "== wire-codec subset (full): chaos, reshard, telemetry, spec =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_wire.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_elastic_subset_quick() {
  echo "== elastic subset (fast): reshard unit + manifest round-trip =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q \
      -k 'reshard or manifest' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_elastic_subset_full() {
  echo "== elastic subset (full): cross-mesh resume goldens + integrity =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_serve_subset_full() {
  echo "== serve full subset (incl. kill-9 queue replay) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_overload_subset_quick() {
  echo "== overload subset (fast): auth, shed, deadline, watchdog, pressure, autoscaler =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py -q \
      -k 'auth or shed or deadline or stall or disk or autoscaler or retry_after or healthz' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_overload_subset_full() {
  echo "== overload subset (full): cancel races, kill -9 cancelled replay, fleet no-resurrect =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_dist_subset_quick() {
  echo "== dist subset (fast): watchdog/heartbeat/fence fakes, fault kinds, launcher units =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_dist.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_dist_subset_full() {
  echo "== dist subset (full): multi-process goldens (peer_kill shrink-and-resume, peer_hang watchdog) =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_dist.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_obsdist_subset_quick() {
  echo "== obsdist subset (fast): sync observer, federation renderer, trace-dir merge, straggler units =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_obsdist.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_obsdist_subset_full() {
  echo "== obsdist subset (full): multi-process stitched-trace golden + straggler attribution + federation chaos =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_obsdist.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_stream_subset_quick() {
  echo "== stream subset (fast): tailer/cutter units + incremental goldens + watermark/lag =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_stream.py -q \
      -m 'not slow' -k 'not kill9 and not fleet and not serve' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_stream_subset_full() {
  echo "== stream subset (full): kill -9 exactly-once, serve surface, fleet stream takeover =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_stream.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_cache_subset_quick() {
  echo "== caching-tier subset (fast): CAS store units + memo key/verify =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_cas.py tests/test_memo.py -q \
      -m 'not slow' -k 'not fleet and not restart and not exactness' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_cache_subset_full() {
  echo "== caching-tier subset (full): warm-restart/fleet memo hits, corruption fallback, GC replay =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_cas.py tests/test_memo.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_apps_subset_quick() {
  echo "== apps subset (fast): invertedindex + graph commands, sans goldens =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_invertedindex.py \
      tests/test_graph_commands.py -q \
      -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

run_apps_subset_full() {
  echo "== apps subset (full): multi-batch corpus + mesh stays-on-device goldens =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_invertedindex.py \
      tests/test_graph_commands.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_fleet_subset_quick() {
  echo "== fleet subset (fast): lease/claim/ring units + router + satellites =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q \
      -k 'lease or epoch or claim or ring or owner_of or retry_after or healthz or refused or redirect' \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

run_fleet_subset_full() {
  echo "== fleet subset (full): kill -9 failover goldens + degraded router =="
  env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly
}

if [ "${1:-}" = "lint" ]; then
  run_lint_full
  exit 0
fi

if [ "${1:-}" = "fleet" ]; then
  run_fleet_subset_full
  exit 0
fi

if [ "${1:-}" = "dist" ]; then
  run_dist_subset_quick
  run_dist_subset_full
  exit 0
fi

if [ "${1:-}" = "obsdist" ]; then
  run_obsdist_subset_quick
  run_obsdist_subset_full
  exit 0
fi

if [ "${1:-}" = "stream" ]; then
  run_stream_subset_quick
  run_stream_subset_full
  exit 0
fi

if [ "${1:-}" = "cache" ]; then
  run_cache_subset_quick
  run_cache_subset_full
  exit 0
fi

if [ "${1:-}" = "apps" ]; then
  run_apps_subset_quick
  run_apps_subset_full
  exit 0
fi

if [ "${1:-}" = "quick" ]; then
  run_lint_quick
  run_plan_subset
  run_metrics_subset
  run_exec_subset
  run_ft_subset
  run_serve_subset_quick
  run_overload_subset_quick
  run_fleet_subset_quick
  run_dist_subset_quick
  run_obsdist_subset_quick
  run_cache_subset_quick
  run_stream_subset_quick
  run_context_subset
  run_elastic_subset_quick
  run_wire_subset_quick
  run_megafuse_subset_quick
  exit 0
fi

echo "== tier-1 (ROADMAP.md) =="
rm -f /tmp/_t1.log
rc=0
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log || rc=$?
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
[ "$rc" -eq 0 ] || exit "$rc"

run_lint_full
run_plan_subset
run_metrics_subset
run_exec_subset
run_ft_subset
run_serve_subset_full
run_overload_subset_full
run_fleet_subset_full
run_dist_subset_full
run_obsdist_subset_full
run_cache_subset_full
run_stream_subset_full
run_context_subset
run_elastic_subset_full
run_wire_subset_full
run_megafuse_subset_full
