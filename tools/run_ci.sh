#!/usr/bin/env bash
# Single build-and-test driver (the paddle_build.sh role, sized to this
# repo): native C++ build + its unit tests, the Python suite on the
# 8-device virtual CPU mesh, the linters, the two crash smokes the tests
# also spawn, and the driver's multichip dryrun. Runs everything by
# default; pass stage names (native|python|lint|conclint|forensics|chaos|
# dryrun) to run a subset. Timings come from perfbench/ on the chip
# (BENCHMARK.json's command), never from here.
#
#   tools/run_ci.sh                      # everything
#   tools/run_ci.sh python               # just pytest
#   tools/run_ci.sh lint                 # verifier+linter over goldens
set -euo pipefail
cd "$(dirname "$0")/.."

ALL_STAGES=(native python lint conclint forensics chaos dryrun)
stages=("$@")
[ ${#stages[@]} -eq 0 ] && stages=("${ALL_STAGES[@]}")
for s in "${stages[@]}"; do
  case " ${ALL_STAGES[*]} " in
    *" $s "*) ;;
    *) echo "unknown stage '$s' (valid: ${ALL_STAGES[*]})" >&2; exit 2 ;;
  esac
done

want() {
  local s
  for s in "${stages[@]}"; do [ "$s" = "$1" ] && return 0; done
  return 1
}

if want native; then
  echo "== native build + C++ tests =="
  cmake -S native -B native/build -G Ninja >/dev/null
  cmake --build native/build >/dev/null
  ./native/build/ptpu_native_test
fi

if want python; then
  echo "== python suite (8-device virtual CPU mesh) =="
  # force-merge the device-count flag: a pre-set XLA_FLAGS would defeat
  # conftest.py's setdefault and silently shrink the mesh to 1 device
  merged="--xla_force_host_platform_device_count=8"
  for tok in ${XLA_FLAGS:-}; do
    case "$tok" in
      --xla_force_host_platform_device_count=*) ;;
      *) merged="$merged $tok" ;;
    esac
  done
  XLA_FLAGS="$merged" JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q
fi

if want lint; then
  echo "== program verifier + retrace-hazard lint (golden models) =="
  # every registry model must verify structurally clean; warnings print
  # but only error-severity findings (bad graphs) fail the stage
  JAX_PLATFORMS=cpu \
    python tools/plint.py --goldens --fail-on=error
fi

if want conclint; then
  echo "== host-plane concurrency lint =="
  # the C-rule lint over the framework's OWN source — lock-order cycles,
  # locks held across blocking calls, untimed acquires reachable from
  # signal handlers, unnamed threads (docs/ANALYSIS.md, *Host-plane
  # concurrency*); the tree must be clean (real fix or reasoned
  # suppression) at error severity. The runtime twin, a real frontend
  # under the lock witness, is a case of tests/test_frontend.py.
  JAX_PLATFORMS=cpu \
    python tools/locklint.py paddle_tpu/ --fail-on=error
fi

if want forensics; then
  echo "== forensics smoke (black box + NaN provenance) =="
  # two child processes crash on purpose: one goes NaN under
  # FLAGS_check_nan_inf (the black box must blame the exact op and
  # blackbox_dump.py must exit non-zero on it), one SIGTERMs itself
  # mid-run (must die BY the signal and still leave a readable dump)
  JAX_PLATFORMS=cpu \
    python tools/forensics_smoke.py
fi

if want chaos; then
  echo "== chaos smoke (crash/resume + retry + corruption) =="
  # three child legs: a SIGKILLed trainer must resume from the newest
  # COMPLETE checkpoint with a bit-identical loss trajectory; a run with
  # injected transient dispatch faults must finish with
  # paddle_tpu_retries_total > 0 and retry events in the black box; a
  # corrupted latest checkpoint must be quarantined and the previous
  # serial loaded (chaos_smoke.py asserts all of it)
  JAX_PLATFORMS=cpu \
    python tools/chaos_smoke.py
fi

if want dryrun; then
  echo "== multichip dryrun (dp+ZeRO / tp / sp / pp) =="
  JAX_PLATFORMS=cpu \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
fi

echo "CI OK"
