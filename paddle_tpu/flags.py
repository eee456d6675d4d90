"""Global flag system read from FLAGS_* environment variables.

Reference parity: the gflags DEFINE_*/--tryfromenv surface
(``python/paddle/fluid/__init__.py:111-133`` whitelists flags and reads
them from env; C++ point-of-use DEFINE_bool/int in executor.cc, malloc.cc,
gpu_info.cc). Same contract here: ``FLAGS_check_nan_inf=1`` in the
environment flips the flag at import (or via ``refresh_from_env``), and
code reads ``flags.get("check_nan_inf")`` at point of use.
"""

import os

__all__ = ["get", "set_flag", "refresh_from_env", "all_flags"]

# name -> (default, parser)
_DEFS = {
    # numeric guards (operator.cc:754 FLAGS_check_nan_inf)
    "check_nan_inf": (False, bool),
    # per-op sync + memory print (executor.cc FLAGS_benchmark)
    "benchmark": (False, bool),
    # forced rematerialization for all grad ops (memory_optimize's lever)
    "remat_gradients": (False, bool),
    # route dynamic_lstm through the fused Pallas recurrence kernel
    # (kernels/lstm_cell.py); opt-in until measured on hardware
    "use_pallas_lstm": (False, bool),
    # same for dynamic_gru (kernels/gru_cell.py)
    "use_pallas_gru": (False, bool),
    # lower conv2d internally in NHWC (transpose sandwich; adjacent
    # sandwiches cancel under XLA) — the layout experiment for the MFU
    # push; numerics identical, measured per-hardware
    "conv_nhwc": (False, bool),
    # override scaled_dot_product_attention's impl="auto" resolution:
    # "auto" (backend picks), "pallas" (force flash kernel), "reference"
    # (XLA-composed attention) — the escape hatch when the Pallas compile
    # path is unavailable/slow on a given rig
    "attention_impl": ("auto", str),
    # ragged paged-attention decode (kernels/paged_attention.py) impl
    # resolution for paged_attention's impl="auto": "auto" (Pallas kernel
    # on TPU targets, composed gather+softmax reference on CPU), "pallas"
    # (force the kernel — interpret mode on CPU, the test path),
    # "reference" (force the composed path everywhere)
    "paged_attention": ("auto", str),
    # beam-decode hypothesis reorder over the paged slot pool
    # (serving/generation.py SlotDecodeSession(beam_width=K)):
    # "rebind" (default) executes the per-step parent permutation as
    # page-table row rebinds + host refcount moves — a pure permutation
    # copies ZERO KV bytes; "reference" is the in-tree copy-reorder
    # oracle (every surviving hypothesis physically copies its parent's
    # resident pages, the pre-paged-attention baseline) — bit-identical
    # tokens, O(T) bytes per reorder. The oracle needs ~beam_width * pages_per_slot free-page
    # headroom for its transient copies; size num_pages accordingly.
    "beam_reorder": ("rebind", str),
    # backward pass of the flash kernel: "pallas" (FlashAttention-2-style
    # dkv/dq kernels, O(block) memory) or "reference" (recompute through
    # the XLA-composed path — materializes the [T, S] score matrix)
    "flash_backward": ("pallas", str),
    # AOT executable image dir (core/exec_cache.py), shared across
    # processes; empty turns that layer off. Setting it also turns on
    # JAX's persistent compile cache, which is placed from OUTSIDE:
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache —
    # never under this flag's directory
    "exec_cache_dir": ("", str),
    # TOTAL byte budget for the persistent cache dir (-1 = unbounded),
    # split evenly: LRU eviction on the XLA layer, oldest-first trim on
    # the AOT image layer
    "exec_cache_max_bytes": (-1, int),
    # step telemetry (observability/telemetry.py): per-step wall time,
    # feed/fetch bytes, transfer seconds, device memory and MFU recorded
    # by every executor run; off = zero hot-path overhead (module bool)
    "telemetry": (False, bool),
    # where the Prometheus scrape + step JSONL land at exit / flush():
    # <path> gets the text-format metrics, <path>.steps.jsonl the per-step
    # records; empty disables the files (in-memory registry stays live)
    "metrics_path": ("", str),
    # MFU accounting override, TFLOP/s: 0 = from device_kind (the one
    # chip table, observability/telemetry.CHIP_PEAKS); set explicitly on
    # hardware the table doesn't know — without it such a device
    # reports NO MFU rather than another chip's
    "peak_tflops": (0.0, float),
    # run the structural program verifier (analysis/verify.py) before
    # every fresh compile in Executor.run/run_multi_step, at Predictor
    # load, and after every transpiler: malformed graphs fail with
    # structured diagnostics instead of XLA tracebacks. Opt-in — the
    # verifier walk is O(ops) per fresh compile, never per step.
    "verify_program": (False, bool),
    # crash black box (observability/blackbox.py): the post-mortem role of
    # the reference's FLAGS_call_stack_level + glog FATAL dumps — a JSON
    # file of the recent flight events (dispatches, recompiles, exceptions,
    # flag snapshot) written on unhandled executor/Predictor exceptions,
    # fatal signals (SIGTERM/SIGABRT), the watchdog, or blackbox.dump();
    # empty disables the recorder (zero hot-path overhead)
    "blackbox_path": ("", str),
    # hang watchdog (observability/watchdog.py): start the background
    # progress monitor at import — the ExceptionHolder-promptness role
    # (framework/details/exception_holder.h) for hangs XLA never surfaces
    # (a stuck collective, a wedged fetch). Opt-in; watchdog.start() is
    # the programmatic switch.
    "watchdog": (False, bool),
    # seconds without executor/fetch progress before the watchdog declares
    # a hang (dumps thread stacks + black box); 0 = auto — a multiple of
    # telemetry's p95 step time when available, else 300s
    "watchdog_timeout": (0.0, float),
    # after a declared hang: dump, then abort the process (os.abort) so a
    # supervisor restarts it instead of burning TPU-hours wedged — the
    # fail-fast discipline of the reference's PADDLE_ENFORCE FATALs
    "watchdog_abort": (False, bool),
    # NaN provenance (observability/nan_provenance.py): when the
    # FLAGS_check_nan_inf on-device scan trips, replay the step per-op
    # from a pre-step state snapshot and blame the FIRST op whose output
    # is non-finite (operator.cc:754's per-op check, paid only after a
    # trip instead of every step). Costs one device-side copy of the
    # mutable state per step while check_nan_inf is on.
    "nan_provenance": (True, bool),
    # periodic checkpointing cadence for resilience.TrainSession, in
    # steps (reference: io.py CheckpointConfig.save_interval_secs role,
    # step-keyed here because TPU steps are the natural clock); 0 = only
    # explicit/final/signal checkpoints
    "checkpoint_interval_steps": (0, int),
    # same cadence on a wall-clock basis, seconds; whichever of the two
    # intervals fires first wins, 0 disables this one
    "checkpoint_interval_secs": (0.0, float),
    # checkpoint retention for resilience.CheckpointManager (reference:
    # CheckpointConfig.max_num_checkpoints); older complete serials
    # beyond this count are pruned after each successful save
    "checkpoint_max_to_keep": (3, int),
    # classified-transient retry budget (resilience/retry.py) applied to
    # the executor fresh-compile/dispatch paths — the listen_and_serv/
    # grpc retry discipline the reference buries in brpc channel
    # options; 0 disables dispatch retrying (zero hot-path overhead
    # beyond one flag read). MasterClient's reconnect-and-retry-once
    # across a master restart is fixed, not governed by this flag.
    "dispatch_retries": (0, int),
    # base of the exponential backoff between retries, seconds (each
    # attempt waits base * 2^attempt plus up to 50% jitter)
    "retry_backoff_s": (0.05, float),
    # deterministic fault injection (resilience/chaos.py): a spec like
    # "seed=7;kill@step=12;io@site=ckpt.write,p=0.5" arms seeded
    # kill-points and injected IO/compile/slow faults at named sites —
    # the chaos-monkey harness the crash/resume CI stage drives; empty
    # disables (module-bool guard, zero overhead)
    "chaos_spec": ("", str),
    # speculative decoding over the paged slot pool
    # (serving/generation.py SlotDecodeSession(speculative=...)): "on"
    # (default) runs the draft/verify tree dispatch when the session was
    # built speculative; "off" is the bit-exactness oracle — the session
    # falls back to the plain one-token step program and the accepted
    # token streams of the two modes must be BIT-identical (greedy exact,
    # sampled via the (seed, slot, position) key scheme). Read at every
    # step, so tests can flip it mid-session without rebuilding.
    "speculative": ("on", str),
    # tree-attention verify kernel (kernels/paged_attention.py
    # paged_tree_attention) impl resolution for impl="auto": "auto"
    # (Pallas kernel on TPU targets, composed gather+ancestor-mask
    # reference on CPU), "pallas" (force the kernel — interpret mode on
    # CPU, the test path), "reference" (force the composed path)
    "tree_attention": ("auto", str),
    # request-scoped distributed tracing across the serving plane
    # (observability/tracing.py): ServingClient mints a trace id that
    # rides the JSON-lines envelope; frontend + decode session record
    # per-request span waterfalls (queue/admit/prefill/dispatch/flush)
    # into a bounded ring, exported as <metrics_path>.traces.jsonl and
    # rendered by tools/trace_view.py. Module-bool guard, same contract
    # as FLAGS_telemetry: off = zero per-request allocations, zero wire
    # bytes, zero fresh-compile delta
    "request_tracing": (False, bool),
    # runtime lock witness (observability/lock_witness.py): named-lock
    # registration wrappers around every framework lock record per-thread
    # acquisition-order edges into a global graph, flag lock-order cycles
    # (potential deadlock) and holds spanning a device dispatch, and
    # annotate blackbox/watchdog thread dumps with which named locks each
    # thread holds. Module-bool guard read at lock CONSTRUCTION time: off
    # (default) means every factory returns a plain threading primitive —
    # zero wrapper allocations, zero per-acquire overhead. Arm via the
    # environment (FLAGS_lock_witness=1) before import, or
    # lock_witness.enable() before the subsystems under test build.
    "lock_witness": (False, bool),
    # training-step observatory (observability/step_profiler.py):
    # phase-attributed per-step records (input wait / feed / compile /
    # dispatch / device / fetch) for Executor.run / run_multi_step /
    # ParallelExecutor, with achieved-FLOP/s and achieved-MFU joined from
    # the hlo_cost_model fused-group table, an online median+MAD step-time
    # regression detector that names the guilty phase, and a JSONL export
    # (<metrics_path>.stepprof.jsonl) the perf ledger ingests. Module-bool
    # guard, same contract as FLAGS_telemetry: off = one attribute read
    # per step, zero allocations, zero fresh-compile delta.
    "step_profile": (False, bool),
}


def _parse(raw, parser):
    if parser is bool:
        return str(raw).lower() in ("1", "true", "yes", "on")
    return parser(raw)


_values = {}


def refresh_from_env():
    """Re-read every FLAGS_<name> env var (init_gflags --tryfromenv)."""
    for name, (default, parser) in _DEFS.items():
        raw = os.environ.get("FLAGS_" + name)
        _values[name] = _parse(raw, parser) if raw is not None else default


def get(name):
    if name not in _DEFS:
        raise KeyError("unknown flag %r (known: %s)"
                       % (name, sorted(_DEFS)))
    return _values[name]


def set_flag(name, value):
    if name not in _DEFS:
        raise KeyError("unknown flag %r" % name)
    _values[name] = _parse(value, _DEFS[name][1])


def all_flags():
    return dict(_values)


refresh_from_env()
