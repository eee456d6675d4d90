"""ParallelExecutor: multi-device (and multi-host) training via GSPMD.

Reference parity: python/paddle/fluid/parallel_executor.py +
paddle/fluid/framework/parallel_executor.cc:58. The reference builds
per-device SSA graphs with inserted NCCL allreduce ops and runs them with a
threaded dataflow scheduler; here the SAME program is jit-compiled once
over a jax.sharding.Mesh with a ShardingPolicy — XLA emits the fused
per-device program plus ICI/DCN collectives, and runs it on all devices
(no host-side scheduler needed).

BuildStrategy.ReduceStrategy maps to the policy:
  AllReduce -> replicated params (grad allreduce), build_strategy.h:55
  Reduce    -> fsdp over the DERIVED sharding plan: the sharding
               transpiler (parallel/sharding.derive_sharding) walks the
               op graph and picks a per-var PartitionSpec over the
               (data, fsdp, tp) mesh — reduce-scatter + all-gather,
               ZeRO-ish — instead of the old blanket dim-0 sharding.
               Hand-written ``sharding_overrides`` naming the legacy
               "model"/"pipe" axes keep the legacy blanket policy.
num_trainers/trainer_id (NCCL2 multi-node) -> jax.distributed processes.

Tensor parallelism needs NO hand-written layout: pass ``tp=`` (and/or
``fsdp=``) and the transpiler derives Megatron column/row splits from
the graph; ``sharding_overrides`` remain an *override* on top of the
derived plan, validated by analysis rule S001 at transpile time.
"""

import threading
import time
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import framework
from paddle_tpu import profiler as _profiler
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import blackbox as _blackbox
from paddle_tpu.observability import explain as _explain
from paddle_tpu.observability import lock_witness
from paddle_tpu.observability import memory as _memory
from paddle_tpu.observability import step_profiler as _stepprof
from paddle_tpu.observability import telemetry as _telemetry
from paddle_tpu.resilience import chaos as _chaos
from paddle_tpu.resilience import retry as _retry
from paddle_tpu.core.fingerprint import (
    executable_key,
    program_fingerprint,
    trace_flags_key,
)
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.core.lowering import (
    CompiledProgram,
    step_key,
    step_key_value,
)
from paddle_tpu.executor import global_scope
from paddle_tpu.parallel.mesh import ShardingPolicy, build_mesh


# Process-global GSPMD executable registry (the executor.py shared-
# registry idiom, mesh-aware): content-addressed keys extended with the
# mesh's device identity and every policy input, so a ParallelExecutor
# REBUILT over the same devices — the elastic runtime tears one down and
# rebuilds per membership generation — reuses the compiled sharded
# executable instead of paying a fresh XLA compile. A fleet that
# reshapes 2 -> 1 -> 2 compiles twice, not three times.
_shared_compiled = OrderedDict()
_shared_lock = lock_witness.make_lock("parallel_executor.shared_cache")
_SHARED_CAP = 32


class ExecutionStrategy(object):
    """execution_strategy.h:21 parity (scheduler knobs are no-ops under XLA,
    kept for API compat)."""

    class ExecutorType(object):
        Default = 0
        Experimental = 1

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100
        self.use_experimental_executor = False


class BuildStrategy(object):
    """build_strategy.h:34 parity."""

    class ReduceStrategy(object):
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy(object):
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        )
        self.debug_graphviz_path = ""
        self.enable_data_balance = False
        self.fuse_elewise_add_act_ops = False


def _names_legacy_axes(sharding_overrides):
    """True when any hand-written override references a legacy-mesh axis
    ("model"/"pipe", or "data" — which the Reduce planning mesh shrinks
    to size 1, so an old `('data', …)` layout would silently stop
    sharding there). Those layouts predate the planning (data, fsdp, tp)
    vocabulary and keep the legacy blanket policy. Malformed specs
    return False so the planning path's S001 validation names the actual
    problem."""
    from paddle_tpu.analysis.shard_check import spec_axes

    for spec in (sharding_overrides or {}).values():
        try:
            if set(spec_axes(spec)) & {"model", "pipe", "data"}:
                return True
        except ValueError:
            pass
    return False


def _warn_noop_strategy_knobs(build_strategy, exec_strategy):
    """Tell the user, once, when they set a knob the XLA execution model
    makes meaningless (docs/XLA_EXECUTION.md has the per-knob rationale)."""
    import warnings

    noop = []
    bs_defaults = BuildStrategy()
    # unlike reduce_strategy (honored in _shard_grad_outputs), these two
    # never reach the lowering — changing them would silently change
    # nothing, so say so
    for f in ("gradient_scale_strategy", "enable_data_balance"):
        if getattr(build_strategy, f, None) != getattr(bs_defaults, f):
            noop.append("BuildStrategy.%s" % f)
    defaults = ExecutionStrategy()
    for f in ("num_threads", "allow_op_delay", "num_iteration_per_drop_scope",
              "use_experimental_executor"):
        if getattr(exec_strategy, f, None) != getattr(defaults, f):
            noop.append("ExecutionStrategy.%s" % f)
    if noop:
        warnings.warn(
            "%s have no effect: the whole program compiles to one XLA "
            "executable, which owns scheduling and elementwise fusion — "
            "see docs/XLA_EXECUTION.md" % ", ".join(noop),
            UserWarning, stacklevel=3)


class ParallelExecutor(object):
    def __init__(
        self,
        use_cuda=False,
        loss_name=None,
        main_program=None,
        share_vars_from=None,
        exec_strategy=None,
        build_strategy=None,
        num_trainers=1,
        trainer_id=0,
        scope=None,
        use_tpu=True,
        num_devices=None,
        model_sharded_vars=None,
        sharding_overrides=None,
        pipeline_stages=None,
        pipeline_microbatches=None,
        fsdp=None,
        tp=None,
    ):
        self._program = main_program or framework.default_main_program()
        self._scope = scope or global_scope()
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        _warn_noop_strategy_knobs(self._build_strategy, self._exec_strategy)
        if getattr(self._build_strategy, "fuse_elewise_add_act_ops", False):
            # fuse_elewise_add_act_pass.cc role: collapse add+act (and the
            # backward twin) into fused ops before compiling the program
            from paddle_tpu.core.passes import apply_pass

            self._program = apply_pass(self._program, "fuse_elewise_add_act")
        self._loss_name = loss_name
        self._cache = {}
        self._run_counter = 0
        self._base_seed = np.random.randint(0, 2**31 - 1)

        # Multi-trainer (NCCL2-mode parity): each trainer is one
        # jax.distributed process; the mesh spans the GLOBAL device list and
        # XLA's collectives cross hosts the way gen_nccl_id-bootstrapped
        # ncclAllReduce did (gen_nccl_id_op.cc:31, nccl_helper.h:103-120).
        self._num_trainers = int(num_trainers)
        self._trainer_id = int(trainer_id)
        if self._num_trainers > 1:
            if jax.process_count() != self._num_trainers:
                raise RuntimeError(
                    "num_trainers=%d but jax.process_count()=%d — call "
                    "paddle_tpu.parallel.init_distributed(coordinator, "
                    "num_processes, process_id) before ParallelExecutor"
                    % (self._num_trainers, jax.process_count())
                )
            if jax.process_index() != self._trainer_id:
                raise RuntimeError(
                    "trainer_id=%d does not match jax.process_index()=%d"
                    % (self._trainer_id, jax.process_index())
                )
            # All trainers must agree on the step-PRNG base seed when the
            # program has none (dropout/random ops would diverge).
            from jax.experimental import multihost_utils

            self._base_seed = int(
                multihost_utils.broadcast_one_to_all(
                    np.int64(self._base_seed)
                )
            )

        devices = jax.devices()
        non_cpu = [d for d in devices if d.platform != "cpu"]
        pool = non_cpu if (use_tpu and non_cpu) else devices
        n = num_devices or len(pool)
        # Program-level pipeline parallelism: cut the Program into S
        # stages over the mesh's pipe axis (parallel/program_pipeline.py);
        # remaining devices form the data axis (pipeline x dp).
        self._pipeline_stages = pipeline_stages
        self._pipeline_micro = pipeline_microbatches or (
            2 * pipeline_stages if pipeline_stages else None)
        self._pipeline_entry = None
        if pipeline_stages:
            if n % pipeline_stages:
                raise ValueError(
                    "pipeline_stages=%d must divide the device count %d"
                    % (pipeline_stages, n))
            if self._num_trainers > 1:
                raise NotImplementedError(
                    "pipeline_stages does not yet compose with "
                    "num_trainers>1 (multi-host feed assembly is only "
                    "wired for the data-parallel path)")
            if fsdp is not None or tp is not None:
                raise NotImplementedError(
                    "pipeline_stages does not yet compose with a "
                    "fsdp/tp planning mesh (pipe-axis composition is an "
                    "open ROADMAP item); drop fsdp=/tp= or the pipeline")
            self.mesh = build_mesh(
                num_devices=n, data=n // pipeline_stages,
                pipe=pipeline_stages, devices=pool)
        elif fsdp is not None or tp is not None:
            # explicit planning mesh: the sharding transpiler derives the
            # full var->PartitionSpec plan over (data, fsdp, tp)
            self.mesh = build_mesh(
                num_devices=n, fsdp=fsdp, tp=tp, devices=pool)
        elif (self._build_strategy.reduce_strategy
              == BuildStrategy.ReduceStrategy.Reduce
              and not model_sharded_vars
              and not _names_legacy_axes(sharding_overrides)):
            # Reduce = "fsdp over the derived plan": batch shards over the
            # fsdp axis exactly as it sharded over "data" before, but the
            # per-var layouts now come from the op graph (conv filters
            # out-channel-sharded, norm stats replicated, tiny biases
            # whole) instead of blanket dim-0 sharding. Legacy-axis
            # overrides / model_sharded_vars keep the old policy.
            self.mesh = build_mesh(num_devices=n, fsdp=n, devices=pool)
        else:
            self.mesh = build_mesh(num_devices=n, devices=pool)
        self._model_sharded_vars = set(model_sharded_vars or ())
        # Tensor-parallel layout control: var name -> PartitionSpec (or a
        # plain tuple of axis names / None). GSPMD inserts the matching
        # collectives (all-gather for column-parallel, psum for
        # row-parallel) — the scaling-book recipe. Under a planning mesh
        # these are OVERRIDES on top of the derived plan (S001-validated);
        # under a legacy mesh they are the whole tensor-parallel story.
        self._sharding_overrides = dict(sharding_overrides or {})
        self._derived_plans = {}  # plan cache: one derivation per compile key
        self._active_plan = None  # plan of the latest compiled executable
        self._overrides_checked = set()  # S001 once per (mesh sig)
        if share_vars_from is not None:
            self._scope = share_vars_from._scope

    @property
    def device_count(self):
        return int(np.prod(list(self.mesh.shape.values())))

    def _policy(self, state_shapes, feed_specs=None):
        if "fsdp" in self.mesh.shape or "tp" in self.mesh.shape:
            return self._derived_policy(state_shapes, feed_specs)
        self._check_overrides_s001()
        strategy = (
            "reduce"
            if self._build_strategy.reduce_strategy
            == BuildStrategy.ReduceStrategy.Reduce
            else "all_reduce"
        )
        from jax.sharding import PartitionSpec

        overrides = {
            name: spec if isinstance(spec, PartitionSpec)
            else PartitionSpec(*spec)
            for name, spec in self._sharding_overrides.items()
        }
        return ShardingPolicy(
            self.mesh,
            strategy=strategy,
            state_shapes=state_shapes,
            model_sharded_vars=self._model_sharded_vars,
            overrides=overrides,
        )

    def _check_overrides_s001(self):
        """Rule S001 on the hand-written override surface (legacy path;
        the derived path validates inside derive_sharding): an override
        naming an unknown var, exceeding its rank, or referencing an axis
        absent from the mesh dies HERE as a rule-tagged Diagnostic, not
        as an opaque XLA shape error minutes into the compile."""
        if not self._sharding_overrides:
            return
        mesh_sig = tuple(sorted(self.mesh.shape.items()))
        if mesh_sig in self._overrides_checked:
            return
        from paddle_tpu.analysis.diagnostics import (
            ProgramVerifyError, at_or_above)
        from paddle_tpu.analysis.shard_check import check_sharding

        diags = check_sharding(
            self._program, self.mesh, self._sharding_overrides,
            origin="sharding_overrides")
        errors = at_or_above(diags, "error")
        if errors:
            raise ProgramVerifyError(errors, origin="ParallelExecutor")
        self._overrides_checked.add(mesh_sig)

    def _derived_policy(self, state_shapes, feed_specs=None):
        """The sharding transpiler path: derive (and cache) the plan for
        this (program, mesh, feed shapes, overrides) key, export its
        per-axis collective-byte gauges, and wrap it in the policy
        interface the CompiledProgram consumes."""
        from paddle_tpu.parallel.sharding import (
            DerivedShardingPolicy,
            derive_sharding,
            record_collective_bytes,
        )

        feed_shapes = {n: s for n, (s, _d) in (feed_specs or {}).items()}
        key = (
            program_fingerprint(self._program),
            tuple(sorted(self.mesh.shape.items())),
            tuple(sorted(feed_shapes.items())),
            tuple(sorted((k, str(v))
                         for k, v in self._sharding_overrides.items())),
        )
        plan = self._derived_plans.get(key)
        if plan is None:
            plan = derive_sharding(
                self._program, self.mesh,
                overrides=self._sharding_overrides or None,
                feed_shapes=feed_shapes)
            record_collective_bytes(plan)
            # bounded FIFO: evict oldest, keep the hot rotation (same
            # idiom as observability.memory's plan registry)
            while len(self._derived_plans) >= 16:
                self._derived_plans.pop(next(iter(self._derived_plans)))
            self._derived_plans[key] = plan
        return DerivedShardingPolicy(self.mesh, plan,
                                     state_shapes=state_shapes)

    def sharding_plan(self, feed_shapes=None):
        """The derived :class:`parallel.sharding.ShardingPlan` this
        executor compiled with — or, before the first run, the plan it
        *would* compile with (planning meshes only; None under a legacy
        mesh) — inspectable without running anything:
        ``debugger.program_to_code`` shows the stamped per-var specs.
        After a run, the no-argument form returns the compiled plan
        verbatim; pass ``feed_shapes`` to derive a what-if plan for
        different feeds (this re-stamps the program annotations)."""
        if not ("fsdp" in self.mesh.shape or "tp" in self.mesh.shape):
            return None
        if feed_shapes is None and self._active_plan is not None:
            return self._active_plan
        feed_specs = {n: (tuple(s), "") for n, s in
                      (feed_shapes or {}).items()}
        return self._derived_policy(
            self._collect_state_shapes(), feed_specs).derived

    def _get_compiled(self, feed_specs, fetch_names):
        scope_names = set(self._scope.local_var_names())
        mesh_sig = tuple(sorted(self.mesh.shape.items()))
        key = (
            # content hash (core/fingerprint.py), not _version alone: two
            # structurally identical programs share the sharded compile
            program_fingerprint(self._program),
            tuple(sorted((n, s, d) for n, (s, d) in feed_specs.items())),
            tuple(fetch_names),
            frozenset(scope_names),
            trace_flags_key(),
            mesh_sig,
        )
        cp = self._cache.get(key)
        if cp is not None:
            exec_cache.record_trace_hit()
            return cp
        # instance miss: consult the process-global registry under a key
        # extended with the mesh's device identity and every policy
        # input the instance key could hold constant — a REBUILT
        # executor (elastic reshape back to a seen world size, Predictor
        # clones, tests constructing fresh PEs) must only reuse an
        # executable whose shardings were derived from identical inputs
        state_shapes = self._collect_state_shapes()
        shared_key = key + (
            tuple(d.id for d in self.mesh.devices.flat),
            self._build_strategy.reduce_strategy,
            tuple(sorted(self._model_sharded_vars)),
            tuple(sorted((k, str(v))
                         for k, v in self._sharding_overrides.items())),
            tuple(sorted(state_shapes.items())),
        )
        with _shared_lock:
            cp = _shared_compiled.get(shared_key)
            if cp is not None:
                _shared_compiled.move_to_end(shared_key)
        if cp is not None:
            exec_cache.record_trace_hit()
            # the reused executable carries the plan it compiled with —
            # this instance adopts it as its active plan
            self._active_plan = getattr(cp, "_sharding_plan", None)
            self._cache[key] = cp
            return cp
        # compile OUTSIDE the registry lock: an XLA compile (plus any
        # retry backoff) must never stall other executors' unrelated
        # cache misses. Two threads racing the same key pay a duplicate
        # compile — exactly what the old per-instance caching always
        # paid — and the loser adopts the winner's entry below.
        exec_cache.record_trace_miss()
        exec_cache.configure()
        _explain.record_compile({
            "program": key[0],
            "feed_specs": tuple(sorted(
                (n, (s, d)) for n, (s, d) in feed_specs.items())),
            "fetch_names": tuple(fetch_names),
            "scope_signature": frozenset(scope_names),
            "flags": key[4],
            "device": "mesh:%s" % (mesh_sig,),
            "mode": "gspmd",
        })
        policy = self._policy(state_shapes, feed_specs)
        self._active_plan = getattr(policy, "derived", None)

        def _build():
            if _chaos.ENABLED:
                _chaos.fault("exec.compile")
            return CompiledProgram(
                self._program,
                feed_specs,
                fetch_names,
                scope_names,
                is_test=self._program._is_test,
                shardings=policy,
            )

        cp = _retry.call(_build, origin="ParallelExecutor.compile")
        # the derived plan rides the executable: memory planning divides
        # predicted bytes by each var's shard factor, and captures/
        # benches read the summary without re-deriving
        cp._sharding_plan = getattr(policy, "derived", None)
        cp._exec_cache_key = executable_key(
            self._program, feed_specs, fetch_names, scope_names,
            extra=("gspmd", mesh_sig,
                   self._build_strategy.reduce_strategy,
                   tuple(sorted(self._model_sharded_vars)),
                   tuple(sorted(
                       (k, str(v))
                       for k, v in self._sharding_overrides.items()
                   ))),
        )
        with _shared_lock:
            existing = _shared_compiled.get(shared_key)
            if existing is not None:
                cp = existing  # a concurrent builder won; use its entry
                self._active_plan = getattr(cp, "_sharding_plan", None)
            else:
                _shared_compiled[shared_key] = cp
                while len(_shared_compiled) > _SHARED_CAP:
                    _shared_compiled.popitem(last=False)
        self._cache[key] = cp
        return cp

    def compiled_text(self):
        """Optimized-HLO text of every sharded executable this executor
        has run (per-device shapes, collectives and kernels as the
        compiler left them) — see ``CompiledProgram.compiled_text``."""
        return [cp.compiled_text() for cp in self._cache.values()]

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        # forensics shell (same contract as Executor.run): armed for the
        # watchdog — a multichip step that never returns is THE hang this
        # layer exists for — and any escaping exception lands in the
        # black box with this origin before propagating
        with _blackbox.guard("ParallelExecutor.run"), \
                _stepprof.DROP_ON_ERROR:
            return self._run_impl(fetch_list, feed, feed_dict, return_numpy)

    def _run_impl(self, fetch_list, feed=None, feed_dict=None,
                  return_numpy=True):
        telem = _telemetry.ENABLED
        prof = _profiler.enabled()
        t0 = time.perf_counter() if (telem or prof) else 0.0
        feed = feed if feed is not None else (feed_dict or {})
        if self._pipeline_stages:
            fetches = self._run_pipeline(fetch_list, feed, return_numpy)
            if telem:
                # per-stage occupancy: the bubble fraction of the GPipe
                # schedule, one labeled series per stage
                _telemetry.record_pipeline_occupancy(
                    self._pipeline_stages, self._pipeline_micro)
                _telemetry.record_step(
                    "pipeline", time.perf_counter() - t0,
                    fingerprint=program_fingerprint(self._program))
            return fetches
        sp = _stepprof.begin("parallel")
        sp.enter("feed")
        if isinstance(feed, list):
            # per-device feed dicts (fluid API) -> concat along batch.
            merged = {}
            for name in feed[0]:
                merged[name] = np.concatenate(
                    [np.asarray(d[name]) for d in feed], axis=0
                )
            feed = merged

        feeds = {}
        feed_specs = {}
        for name, value in feed.items():
            arr = (
                np.asarray(value.numpy())
                if isinstance(value, LoDTensor)
                else np.asarray(value)
            )
            if self._num_trainers > 1:
                # Each trainer feeds its LOCAL batch shard; assemble the
                # global array (this is the FeedAndSplitTensorIntoLocalScopes
                # role, parallel_executor.cc:286, inverted: shards in,
                # global view out). Non-batch feeds replicate (each trainer
                # must pass the full value) per the policy's shape check —
                # the global dim0 for sharded feeds is num_trainers * local.
                policy = self._policy(self._collect_state_shapes())
                gshape = list(arr.shape)
                if gshape:
                    gshape[0] *= self._num_trainers
                sh = policy.feed_sharding(name, shape=tuple(gshape))
                if sh.is_fully_replicated:
                    # every trainer passes the identical full value
                    host = arr
                    arr = jax.make_array_from_callback(
                        host.shape, sh, lambda idx: host[idx]
                    )
                else:
                    arr = jax.make_array_from_process_local_data(sh, arr)
            feeds[name] = arr
            feed_specs[name] = (tuple(arr.shape), str(arr.dtype))

        sp.exit()
        fetch_names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list
        ]
        sp.enter("compile")
        cp = self._get_compiled(feed_specs, fetch_names)
        sp.exit()
        # input assembly continues: state gather (+ reshard) and
        # step-key derivation run on the host clock before dispatch
        sp.enter("feed")

        state = {}
        for n in cp.state_in:
            v = self._scope.find_var(n)
            if v is None or v.value is None:
                raise RuntimeError(
                    "persistable var %r not initialized (run startup first)" % n
                )
            val = v.value
            # State initialized by the single-device startup Executor is
            # committed to one device; donated jit args must already carry
            # the mesh sharding, so reshard explicitly (BCastParamsToDevices
            # role, parallel_executor.cc:180).
            if isinstance(val, jax.Array):
                val = self._ensure_sharded(val, cp.shardings.state_sharding(n))
            state[n] = val

        key = self._step_key()
        sp.exit()
        # opens before the pre-dispatch work (cost snapshot,
        # blackbox record): host dispatch overhead is charged to
        # dispatch, not left in the unattributed residual
        sp.enter("dispatch")
        if _stepprof.ENABLED:
            sp.pre_dispatch(cp, state, feeds, key, self._program)
        flops_avals = None
        mem_dev = None
        if telem:
            fingerprint = _telemetry.executable_fingerprint(
                cp, self._program)
            flops_avals = _telemetry.capture_step_avals(
                cp, state, feeds, key)
            _telemetry.record_device_transfer(
                self._feed_bytes_by_device(cp, feeds))
            # HBM ledger: feeds/fetches (global sharded arrays) book
            # under one 'mesh' label; STATE books per device from real
            # shard sizes below, so the ledger shows each chip's
            # param/opt_state residency under the derived plan
            mem_dev = "mesh"
            _memory.track_feeds(feeds, mem_dev)
            if not getattr(cp, "_memory_plan_done", False):
                shard_factors = mesh_devices = None
                if getattr(cp, "_sharding_plan", None) is not None:
                    from paddle_tpu.parallel.sharding import (
                        plan_shard_factors)

                    shard_factors = plan_shard_factors(cp._sharding_plan)
                    mesh_devices = self.device_count
                _memory.register_plan_for(cp, self._program, feed_specs,
                                          fingerprint,
                                          shard_factors=shard_factors,
                                          mesh_devices=mesh_devices)
        if _blackbox.ENABLED:
            _blackbox.record_dispatch(
                "ParallelExecutor.run", feed_specs=feed_specs,
                fetch_names=fetch_names,
                fingerprint=getattr(cp, "_exec_cache_key", None),
                mesh=dict(self.mesh.shape))
        t_disp = time.perf_counter() if telem else 0.0
        from paddle_tpu.executor import Executor as _Executor

        new_state, fetches = _Executor._dispatch(
            cp, state, feeds, key, origin="ParallelExecutor.dispatch")
        sp.exit()
        sp.enter("fetch")
        for n, val in new_state.items():
            self._scope.set_value(n, val)
        del state  # the donated inputs, released in the bracket
        if telem:
            # per-device ledger entries from the REAL shard sizes: a
            # param fsdp-sharded 4 ways books ~1/4 of its bytes on each
            # device label; replicated state books full bytes on every
            # device — paddle_tpu_hbm_live_bytes{device,kind} shows the
            # derived plan's memory win directly
            _memory.track_state_sharded(cp, self._program, new_state,
                                        fallback_device=mem_dev)
            _memory.track_fetches(cp.fetch_names, fetches, mem_dev)
            _memory.drop_feeds(feeds, mem_dev)
        # the fetch bracket closes AFTER the ledger writeback (see
        # Executor.run): co-enabled telemetry's accounting is
        # output handling, not unattributed residual
        sp.exit()
        device_times = None
        if telem and return_numpy:
            # per-device dispatch->ready latency, measured on the live
            # global arrays BEFORE any host materialization — the
            # straggler/imbalance signal. Only on the return_numpy path,
            # which syncs anyway: blocking per-shard under
            # return_numpy=False would turn an async dispatch into a
            # full per-step device sync and distort the thing measured.
            # This blocks on device shards, so it IS device wait — the
            # bracket charges it there, and the later per-fetch
            # block_until_ready returns instantly having been paid here
            sp.enter("device")
            device_times = _telemetry.device_step_times(
                list(fetches) + list(new_state.values()), t_disp)
            sp.exit()
        if return_numpy:
            sp.enter("device")
            with _stepprof.device_annotation():
                for _f in fetches:
                    if hasattr(_f, "block_until_ready"):
                        _f.block_until_ready()
            sp.exit()
            sp.enter("fetch")
            try:
                fetches = [self._fetch_to_numpy(f) for f in fetches]
            except Exception as exc:
                # allocator deaths can surface at the host read, not the
                # dispatch — same M001 forensics as Executor._dispatch
                if _memory.is_oom(exc) and not isinstance(
                        exc, _memory.MemoryExhaustedError):
                    _memory.enrich_and_raise(
                        exc, origin="ParallelExecutor.fetch")
                raise
            sp.exit()
        # span closes before telemetry's record-keeping tail (see
        # Executor.run): per-step wall is comparable across
        # observer configurations
        _stepprof.finish(sp, feeds=feeds, fetches=fetches)
        if telem:
            _memory.drop_fetches(cp.fetch_names, mem_dev)
        if telem or prof:
            t1 = time.perf_counter()
            if telem:
                _telemetry.record_step(
                    "parallel", t1 - t0,
                    feed_bytes=sum(
                        getattr(a, "nbytes", 0) for a in feeds.values()),
                    fetch_bytes=sum(
                        getattr(f, "nbytes", 0) for f in fetches
                        if hasattr(f, "nbytes")),
                    fingerprint=fingerprint,
                    device_times=device_times)
                if flops_avals is not None:
                    _telemetry.register_flops_from_avals(
                        cp, fingerprint, flops_avals)
            if prof:
                _profiler.record_span("parallel_executor.run", t0, t1)
        return fetches

    def _feed_bytes_by_device(self, cp, feeds):
        """{device label: feed bytes} for one step. Global jax arrays
        report their real addressable shards; host numpy feeds (the
        single-process path — jit shards them at dispatch) are priced
        from the policy's feed sharding, which is what jit applies."""
        from paddle_tpu.parallel.mesh import device_label

        per_dev = {}
        for name, arr in feeds.items():
            if isinstance(arr, jax.Array):
                try:
                    for sh in arr.addressable_shards:
                        lbl = device_label(sh.device)
                        per_dev[lbl] = per_dev.get(lbl, 0) + int(
                            getattr(sh.data, "nbytes", 0))
                    continue
                except Exception:
                    pass
            try:
                sharding = cp.shardings.feed_sharding(
                    name, shape=tuple(arr.shape))
                shard_shape = sharding.shard_shape(tuple(arr.shape))
                nbytes = int(np.prod(shard_shape, dtype=np.int64)
                             ) * arr.dtype.itemsize if shard_shape else \
                    arr.dtype.itemsize
                for d in sharding.addressable_devices:
                    lbl = device_label(d)
                    per_dev[lbl] = per_dev.get(lbl, 0) + nbytes
            except Exception:
                continue
        return per_dev

    # -- program-level pipeline path ---------------------------------------
    def _run_pipeline(self, fetch_list, feed, return_numpy):
        from paddle_tpu.parallel.program_pipeline import PipelinedProgram

        if isinstance(feed, list):
            feed = {
                name: np.concatenate(
                    [np.asarray(d[name]) for d in feed], axis=0)
                for name in feed[0]
            }
        fetch_names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list
        ]
        if self._loss_name and fetch_names and fetch_names != [
                self._loss_name]:
            raise ValueError(
                "pipeline runs fetch only the loss (%r), got %r — params "
                "live packed per stage; use pipeline_sync_scope() to "
                "inspect them" % (self._loss_name, fetch_names))
        feeds = {}
        feed_specs = {}
        for name, value in feed.items():
            arr = (
                np.asarray(value.numpy())
                if isinstance(value, LoDTensor)
                else np.asarray(value)
            )
            feeds[name] = arr
            feed_specs[name] = (tuple(arr.shape), str(arr.dtype))
        sig = (program_fingerprint(self._program),
               tuple(sorted(feed_specs.items())), trace_flags_key())
        entry = self._pipeline_entry
        if entry is None or entry["sig"] != sig:
            if entry is not None:
                # the executable is stale (new feed shapes or program
                # version) but the TRAINED packed state is not: flush it
                # to the scope so the rebuilt entry repacks current values
                self.pipeline_sync_scope()
            pp = PipelinedProgram(
                self._program,
                self._loss_name,
                feed_specs,
                self.mesh,
                self._pipeline_micro,
                batch_axis="data" if self.mesh.shape["data"] > 1 else None,
            )
            entry = {"pp": pp, "state": pp.pack_from_scope(self._scope),
                     "sig": sig}
            self._pipeline_entry = entry
        pp = entry["pp"]
        params, accs, scalars = entry["state"]
        # the pipeline's executable takes the key as a value: one dispatch
        key = step_key_value(self._step_key())
        params, accs, scalars, loss = pp.jitted(
            params, accs, scalars, feeds, key)
        entry["state"] = (params, accs, scalars)
        # scalar persistables (lr counters, beta pows) stay scope-visible
        for n, val in scalars.items():
            self._scope.set_value(n, val)
        if not fetch_names:
            return []
        if return_numpy:
            return [np.reshape(np.asarray(loss), (1,))]
        return [jnp.reshape(loss, (1,))]

    def _step_key(self):
        """As ``Executor._step_key``; the base key stays on the host, the
        mesh executable places it (replicated) with its other arguments."""
        self._run_counter += 1
        return step_key(self._program.random_seed or self._base_seed,
                        self._run_counter)

    def pipeline_sync_scope(self):
        """Unpack the pipeline's packed params/accumulators back into their
        per-name scope vars (so save_persistables etc. see current values)."""
        entry = self._pipeline_entry
        if entry is not None:
            params, accs, _ = entry["state"]
            entry["pp"].unpack_to_scope(self._scope, params, accs)

    def _ensure_sharded(self, val, target):
        """Reshard ``val`` to ``target`` if it is not already equivalent."""
        try:
            if val.sharding.is_equivalent_to(target, val.ndim):
                return val
        except Exception:
            pass
        if (
            self._num_trainers > 1
            and not getattr(target, "is_fully_addressable", True)
            and getattr(val, "is_fully_addressable", True)
        ):
            # First mesh placement of locally-initialized state: broadcast
            # rank 0's value so every trainer materializes shards of the
            # SAME array even when startup init was unseeded — the actual
            # BCastParamsToDevices (parallel_executor.cc:180).
            from jax.experimental import multihost_utils

            host = multihost_utils.broadcast_one_to_all(np.asarray(val))
            host = np.asarray(host)
            return jax.make_array_from_callback(
                host.shape, target, lambda idx: host[idx]
            )
        # Already-global arrays reshard device-side (XLA collectives).
        return jax.device_put(val, target)

    def _fetch_to_numpy(self, f):
        """Fetched global arrays: fully-addressable values read directly;
        otherwise stitch this process's addressable shards (the reference
        likewise fetches trainer-local values in NCCL2 mode)."""
        if not (isinstance(f, jax.Array) and not f.is_fully_addressable):
            return np.asarray(f)
        shards = {}
        for s in f.addressable_shards:
            key = tuple(
                (sl.start or 0, sl.stop) for sl in s.index
            )
            shards.setdefault(key, np.asarray(s.data))
        if len(shards) == 1:
            return next(iter(shards.values()))
        keys = sorted(shards)
        axis = next(
            i for i in range(len(keys[0]))
            if len({k[i] for k in keys}) > 1
        )
        ordered = [shards[k] for k in sorted(shards, key=lambda k: k[axis])]
        return np.concatenate(ordered, axis=axis)

    def _collect_state_shapes(self):
        state_shapes = {}
        for n in self._scope.local_var_names():
            v = self._scope.get_value(n)
            if v is not None and hasattr(v, "shape"):
                state_shapes[n] = tuple(v.shape)
        return state_shapes

    def bcast_params(self):
        """BCastParamsToDevices parity (parallel_executor.cc:180): eagerly
        reshard every initialized scope var onto the mesh per the current
        ShardingPolicy (jit would otherwise do this lazily on first run)."""
        policy = self._policy(self._collect_state_shapes())
        for n in sorted(policy.state_shapes):
            v = self._scope.get_value(n)
            if isinstance(v, jax.Array):
                self._scope.set_value(
                    n, self._ensure_sharded(v, policy.state_sharding(n))
                )
