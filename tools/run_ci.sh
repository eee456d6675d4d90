#!/usr/bin/env bash
# Single build-and-test driver (the paddle_build.sh role, sized to this
# repo): native C++ build + its unit tests, the Python suite on the
# 8-device virtual CPU mesh, the driver's multichip dryrun, and a CPU
# proxy of the benchmark. Runs everything by default; pass stage names
# (native|python|lint|conclint|warm|metrics|forensics|chaos|shard|serve|
# decode|servechaos|route|net|trace|stepprof|elastic|dryrun|bench|
# perfgate) to run a subset.
#
#   tools/run_ci.sh                      # everything
#   tools/run_ci.sh python               # just pytest
#   tools/run_ci.sh lint                 # verifier+linter over goldens
#   BENCH_PLATFORM= tools/run_ci.sh bench   # on a TPU host: real-chip bench
set -euo pipefail
cd "$(dirname "$0")/.."

ALL_STAGES=(native python lint conclint warm metrics forensics chaos shard
            serve decode servechaos route net trace stepprof elastic dryrun
            bench perfgate)
stages=("$@")
[ ${#stages[@]} -eq 0 ] && stages=("${ALL_STAGES[@]}")
for s in "${stages[@]}"; do
  case " ${ALL_STAGES[*]} " in
    *" $s "*) ;;
    *) echo "unknown stage '$s' (valid: ${ALL_STAGES[*]})" >&2; exit 2 ;;
  esac
done

# Cold/warm stages need an EMPTY compile cache for their cold leg and the
# SAME one for their warm leg. JAX's cache is placed from outside
# (core/exec_cache.py), so each such stage says so explicitly: it exports
# JAX_COMPILATION_CACHE_DIR to a fresh directory beside its AOT image dir.
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
  echo "== host-plane concurrency lint + witness-armed frontend smoke =="
  # leg 1: the C-rule lint over the framework's OWN source — lock-order
  # cycles, locks held across blocking calls, untimed acquires reachable
  # from signal handlers, unnamed threads (docs/ANALYSIS.md, *Host-plane
  # concurrency*); the tree must be clean (real fix or reasoned
  # suppression) at error severity
  JAX_PLATFORMS=cpu \
    python tools/locklint.py paddle_tpu/ --fail-on=error
  # leg 2: the runtime twin — rerun the frontend smoke with the lock
  # witness armed (FLAGS_lock_witness=1 wraps every framework lock at
  # construction); the warm leg asserts zero lock-order cycles, zero
  # dispatch-spanning holds, and the same 0-fresh-compiles gate, proving
  # the witness itself perturbs nothing
  cldir="$(mktemp -d)"
  trap 'rm -rf "$cldir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$cldir/cache" \
    JAX_COMPILATION_CACHE_DIR="$cldir/xla" FLAGS_telemetry=1 \
    FLAGS_lock_witness=1 \
    python tools/frontend_smoke.py cold "$cldir"
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$cldir/cache" \
    JAX_COMPILATION_CACHE_DIR="$cldir/xla" FLAGS_telemetry=1 \
    FLAGS_lock_witness=1 \
    python tools/frontend_smoke.py warm "$cldir"
  rm -rf "$cldir"
  trap - EXIT
fi

if want warm; then
  echo "== warm-start smoke (persistent executable cache) =="
  # two subprocesses share one exec_cache_dir; the second must execute
  # the same tiny program with ZERO fresh XLA compiles (asserted via the
  # exec_cache stats counters inside warm_start_smoke.py)
  cache_dir="$(mktemp -d)"
  trap 'rm -rf "$cache_dir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$cache_dir" \
    JAX_COMPILATION_CACHE_DIR="$cache_dir/xla" \
    python tools/warm_start_smoke.py cold
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$cache_dir" \
    JAX_COMPILATION_CACHE_DIR="$cache_dir/xla" \
    python tools/warm_start_smoke.py warm
  rm -rf "$cache_dir"
  trap - EXIT
fi

if want metrics; then
  echo "== metrics smoke (flight recorder scrape) =="
  # two processes share one exec cache dir; each runs a 3-step MLP with
  # telemetry on and must leave a parseable Prometheus file with nonzero
  # paddle_tpu_steps_total; the warm one additionally proves the scrape
  # shows ZERO fresh compiles (metrics_smoke.py asserts all of it)
  mdir="$(mktemp -d)"
  trap 'rm -rf "$mdir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_telemetry=1 FLAGS_metrics_path="$mdir/cold.prom" \
    FLAGS_exec_cache_dir="$mdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$mdir/xla" \
    python tools/metrics_smoke.py cold
  JAX_PLATFORMS=cpu \
    FLAGS_telemetry=1 FLAGS_metrics_path="$mdir/warm.prom" \
    FLAGS_exec_cache_dir="$mdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$mdir/xla" \
    python tools/metrics_smoke.py warm
  rm -rf "$mdir"
  trap - EXIT
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

if want shard; then
  echo "== sharding transpiler smoke (derived data x fsdp x tp plan) =="
  # two processes share one exec cache dir on the 8-virtual-device CPU
  # mesh; each proves derived-plan loss parity with the single-device
  # run (ZERO hand-written tp_layout entries) and 1/N per-device
  # param+opt_state ledger bytes under the fsdp x tp split; the second
  # must additionally execute the SHARDED executable with zero fresh
  # XLA compiles via the persistent exec cache (shard_smoke.py asserts
  # all of it)
  sdir="$(mktemp -d)"
  trap 'rm -rf "$sdir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$sdir" \
    JAX_COMPILATION_CACHE_DIR="$sdir/xla" \
    python tools/shard_smoke.py cold
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$sdir" \
    JAX_COMPILATION_CACHE_DIR="$sdir/xla" \
    python tools/shard_smoke.py warm
  rm -rf "$sdir"
  trap - EXIT
fi

if want serve; then
  echo "== serving smoke (continuous batching, 0 steady-state compiles) =="
  # two processes share one exec cache dir: the cold pass trains + saves
  # the demo model and warms the bucket-ladder executables; the warm one
  # replays a MIXED batch-size load and must scrape ZERO fresh compiles
  # from the metrics registry, prove batched == per-request bit-for-bit,
  # and land a latency capture that perf_diff gates against the
  # committed serving budgets (p99, throughput, occupancy)
  svdir="$(mktemp -d)"
  trap 'rm -rf "$svdir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$svdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$svdir/xla" FLAGS_telemetry=1 \
    python tools/serve_smoke.py cold "$svdir"
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$svdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$svdir/xla" FLAGS_telemetry=1 \
    python tools/serve_smoke.py warm "$svdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$svdir/serve.json" \
      --budgets benchmark/budgets.json --models serving
  rm -rf "$svdir"
  trap - EXIT
fi

if want decode; then
  echo "== paged decode smoke (ragged paged attention, 0 churn compiles) =="
  # one process: churny admit/release/step over the paged slot session
  # must add ZERO fresh compiles after warmup (metrics-registry scrape +
  # exec-cache counters), decode tokens must equal the dense oracle's,
  # and the drained pool must return every KV page; a second leg churns
  # the CROSS-REQUEST reuse paths (best-of-N fork groups + forced
  # divergence/COW + prefix-cache hits + release/re-admit) asserting 0
  # fresh compiles and refcount conservation at drain; a third leg (PR
  # 15) churns staggered BEAM admissions — 0 fresh compiles at warm
  # steady state, zero pages physically moved by rebind reorders, and
  # token/score bit-equality against the FLAGS_beam_reorder=reference
  # copy oracle; a fourth leg (PR 16) churns SPECULATIVE decode —
  # draft/tree-verify/accept/reject waves add 0 fresh compiles after
  # warmup and stream bit-identical to both the dense oracle and a
  # FLAGS_speculative=off replay on the same session; then the bench
  # decode worker lands an A/B capture (paged vs dense tokens/sec at
  # mixed lengths / low occupancy, the shared-vs-unshared best-of-N
  # ratio, prefix hit rate, grouped cross-K/V bytes, beam_speedup /
  # beam_reorder_bytes from the rebind-vs-copy beam A/B, plus
  # speculative_speedup / acceptance_rate from the draft-then-verify
  # vs sequential-oracle A/B) that perf_diff gates against the
  # committed decode budgets
  dcdir="$(mktemp -d)"
  trap 'rm -rf "$dcdir"' EXIT
  JAX_PLATFORMS=cpu FLAGS_telemetry=1 \
    python tools/decode_smoke.py "$dcdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$dcdir/decode.json" \
      --budgets benchmark/budgets.json --models decode
  rm -rf "$dcdir"
  trap - EXIT
fi

if want servechaos; then
  echo "== serving chaos smoke (SIGKILL mid-decode restore + overload) =="
  # leg 1: three subprocesses share one exec cache dir — an oracle
  # decodes a backlog uninterrupted, a snapshotting victim is SIGKILLed
  # entering a seeded step dispatch, and a restored process must re-emit
  # the remaining token streams BIT-identical to the oracle's with ZERO
  # fresh compiles scraped from its metrics registry; leg 2 floods a
  # degradation-armed BatchingServer past shed and asserts only typed
  # retriable rejects, no wedged futures, and a brownout->healthy round
  # trip in the health gauge. The capture (snapshot_seconds +
  # fresh_compiles) gates against the committed servechaos budgets.
  scdir="$(mktemp -d)"
  trap 'rm -rf "$scdir"' EXIT
  JAX_PLATFORMS=cpu FLAGS_telemetry=1 \
    python tools/serve_chaos_smoke.py "$scdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$scdir/servechaos.json" \
      --budgets benchmark/budgets.json --models servechaos
  rm -rf "$scdir"
  trap - EXIT
fi

if want route; then
  echo "== router fleet smoke (SIGKILL-a-frontend failover) =="
  # an oracle subprocess decodes the whole request set and warms one
  # shared exec cache; the parent then runs a ServingRouter over TWO
  # frontend subprocesses, pins duplicate (src, prefix) pairs to one
  # member via affinity hashing (prefix hits must survive the 2-member
  # scale-out), and SIGKILLs one frontend with live slots on board —
  # every concurrent stream must still complete through the router
  # BIT-identical to the oracle (the victim's banked snapshot restores
  # on the survivor, relays re-attach and splice at (rid, seq)) with
  # ZERO lost streams and ZERO fresh compiles on the survivor. The
  # capture gates against the committed router budgets.
  rtdir="$(mktemp -d)"
  trap 'rm -rf "$rtdir"' EXIT
  JAX_PLATFORMS=cpu FLAGS_telemetry=1 \
    python tools/router_smoke.py "$rtdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$rtdir/router.json" \
      --budgets benchmark/budgets.json --models router
  rm -rf "$rtdir"
  trap - EXIT
fi

if want net; then
  echo "== network front-end smoke (wire serving plane, 0 warm compiles) =="
  # two processes share one exec cache dir: the cold leg trains the
  # demo model, warms every executable and banks the IN-PROCESS oracle
  # (predict outputs + token streams incl. a best-of-2 fork and a
  # prefix-cache hit); the warm leg binds a ServingFrontend on a real
  # socket, replays the mixed unary+streaming load through
  # ServingClients and must prove: byte-identical responses/streams vs
  # the oracle, a client killed mid-stream leaves the KV pool at
  # refcount conservation, ZERO fresh compiles in the metrics scrape
  # fetched OVER THE WIRE, and overload shed reaching the client as
  # typed retriable DegradedError with a retry-after hint. The capture
  # (requests/sec, wire p50/p99, ttft_ms) gates against the committed
  # frontend budgets.
  ndir="$(mktemp -d)"
  trap 'rm -rf "$ndir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$ndir/cache" \
    JAX_COMPILATION_CACHE_DIR="$ndir/xla" FLAGS_telemetry=1 \
    python tools/frontend_smoke.py cold "$ndir"
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$ndir/cache" \
    JAX_COMPILATION_CACHE_DIR="$ndir/xla" FLAGS_telemetry=1 \
    python tools/frontend_smoke.py warm "$ndir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$ndir/frontend.json" \
      --budgets benchmark/budgets.json --models frontend
  rm -rf "$ndir"
  trap - EXIT
fi

if want trace; then
  echo "== request-tracing smoke (free when off, complete when on) =="
  # three processes share one exec cache dir: the cold leg warms every
  # decode executable and banks the in-process token-stream oracle; the
  # OFF leg (control) replays the load over a real socket with tracing
  # unset and must prove bit-identical streams, NO trace field on the
  # wire and 0 fresh compiles; the ON leg replays with
  # FLAGS_request_tracing=1 and must prove the streams and compile
  # counters UNCHANGED, one wire-resolvable trace per request whose
  # span union covers >=95% of the client-observed wall, a TTFT
  # histogram exemplar resolving to a ring record, and
  # trace_view/step_breakdown rendering the flushed JSONL (waterfall +
  # valid Perfetto export). The capture (span_coverage,
  # fresh_compiles) gates against the committed trace budgets.
  tdir="$(mktemp -d)"
  trap 'rm -rf "$tdir"' EXIT
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$tdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$tdir/xla" FLAGS_telemetry=1 \
    python tools/trace_smoke.py cold "$tdir"
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$tdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$tdir/xla" FLAGS_telemetry=1 \
    python tools/trace_smoke.py off "$tdir"
  JAX_PLATFORMS=cpu \
    FLAGS_exec_cache_dir="$tdir/cache" \
    JAX_COMPILATION_CACHE_DIR="$tdir/xla" FLAGS_telemetry=1 \
    FLAGS_request_tracing=1 \
    python tools/trace_smoke.py on "$tdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$tdir/trace.json" \
      --budgets benchmark/budgets.json --models trace
  rm -rf "$tdir"
  trap - EXIT
fi

if want stepprof; then
  echo "== step-observatory smoke (free when off, accountable when on) =="
  # one process, two legs over the same seeded training job: the control
  # leg (FLAGS_step_profile unset) banks every fetch and the timed walls;
  # the profiled leg replays the identical schedule and must prove
  # bit-identical fetches, ZERO fresh compiles, >=95% of every step wall
  # attributed to named phases, a finite achieved-FLOP/s join on every
  # training record, and the offline round trip (write_stepprof_jsonl ->
  # step_breakdown --steps -> perf_ledger append/show/diff). The capture
  # (phase_coverage, fresh_compiles, achieved_flops_per_sec,
  # stepprof_overhead) gates against the committed stepprof budgets.
  spdir="$(mktemp -d)"
  trap 'rm -rf "$spdir"' EXIT
  JAX_PLATFORMS=cpu \
    python tools/stepprof_smoke.py "$spdir"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$spdir/stepprof.json" \
      --budgets benchmark/budgets.json --models stepprof
  rm -rf "$spdir"
  trap - EXIT
fi

if want elastic; then
  echo "== elastic smoke (fleet churn: SIGKILL -> evict -> reshard) =="
  # two worker subprocesses + an in-parent FleetCoordinator: worker 1 is
  # SIGKILLed mid-epoch and must be evicted within the lease timeout;
  # the survivor reshards its checkpoint to world 1 and its loss segment
  # must be BIT-identical to a fresh process restored from the same
  # barrier checkpoint; a re-admitted worker joins at the next
  # generation and matches the survivor exactly; the fleet gauges +
  # reshard timings must land in the metrics scrape and the final
  # sharded checkpoint must pass ckpt_inspect --verify. A second leg
  # restarts the coordinator from its snapshot mid-run: heartbeats
  # retry through it with no spurious reshape (elastic_smoke.py asserts
  # all of it)
  JAX_PLATFORMS=cpu \
    python tools/elastic_smoke.py
fi

if want dryrun; then
  echo "== multichip dryrun (dp+ZeRO / tp / sp / pp) =="
  JAX_PLATFORMS=cpu \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
fi

if want bench; then
  # Default cpu: the explicit CPU proxy (tiny shapes, *_cpu_proxy metric
  # names, mfu null). Export BENCH_PLATFORM= (empty) on a TPU host to
  # measure the chip; there a host with no accelerator is an error.
  echo "== benchmark (BENCH_PLATFORM='${BENCH_PLATFORM-cpu}') =="
  # bench.py exits non-zero when a requested model produced no result
  # (set -e stops the stage); the check below also pins the line's shape
  out="$(BENCH_PLATFORM="${BENCH_PLATFORM-cpu}" python bench.py)"
  echo "$out"
  echo "$out" | BENCH_EXPECT="${BENCH_MODELS-${BENCH_MODEL-resnet50,transformer,serving,frontend,decode}}" python -c '
import json, os, sys
rec = json.loads(sys.stdin.readline())
models = rec.get("models") or {}
want = [m.strip() for m in os.environ["BENCH_EXPECT"].split(",") if m.strip()]
missing = [m for m in want if m not in models]
assert not missing, "bench missing results for %s: %s" % (
    missing, rec.get("error"))
'
fi

if want perfgate; then
  echo "== perf/memory regression gate (CPU mini-bench vs budgets) =="
  # the CPU mini-bench runs with telemetry ON so the capture carries
  # step_ms percentiles + the HBM trajectory (peak_hbm_bytes measured by
  # the live-buffer ledger, predicted_peak_bytes from the memory plan);
  # tools/perf_diff.py gates it against the checked-in budgets —
  # deterministic counters (fresh compiles, predicted peak) fail on ANY
  # increase, timings get the budgets' noise band
  gdir="$(mktemp -d)"
  trap 'rm -rf "$gdir"' EXIT
  BENCH_PLATFORM=cpu FLAGS_telemetry=1 python bench.py \
    | tail -1 > "$gdir/candidate.json"
  JAX_PLATFORMS=cpu \
    python tools/perf_diff.py "$gdir/candidate.json" \
      --budgets benchmark/budgets.json
  rm -rf "$gdir"
  trap - EXIT
fi

echo "CI OK"
