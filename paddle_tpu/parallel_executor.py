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

import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import framework
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import blackbox as _blackbox
from paddle_tpu.observability import memory as _memory
from paddle_tpu.observability import telemetry as _telemetry
from paddle_tpu.core.fingerprint import program_fingerprint, trace_flags_key
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.core.lowering import CompiledProgram, step_key_value
from paddle_tpu.executor import (
    _RUN_PARALLEL,
    Executor,
    _fetch_names,
    _run_step,
    _shared_executable,
    global_scope,
)
from paddle_tpu.parallel.mesh import ShardingPolicy, build_mesh


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
                    "fsdp/tp planning mesh (pipe-axis composition is "
                    "ROADMAP R5); drop fsdp=/tp= or the pipeline")
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

    def _get_compiled(self, program, feed_specs, fetch_names, scope,
                      device=None, mode=None, refresh=False):
        scope_names = scope.visible_names()
        mesh_sig = tuple(sorted(self.mesh.shape.items()))
        key = (
            # content hash (core/fingerprint.py), not _version alone: two
            # structurally identical programs share the sharded compile
            program_fingerprint(program),
            ("gspmd", mesh_sig),
            tuple(sorted((n, s, d) for n, (s, d) in feed_specs.items())),
            tuple(fetch_names),
            scope_names,
            trace_flags_key(),
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
        # executable whose shardings were derived from identical inputs.
        # A fleet that reshapes 2 -> 1 -> 2 compiles twice, not three times
        state_shapes = self._collect_state_shapes()
        policy_inputs = (
            self._build_strategy.reduce_strategy,
            tuple(sorted(self._model_sharded_vars)),
            tuple(sorted((k, str(v))
                         for k, v in self._sharding_overrides.items())),
        )

        def build():
            policy = self._policy(state_shapes, feed_specs)
            cp = CompiledProgram(
                program, feed_specs, fetch_names, scope_names,
                is_test=program._is_test, shardings=policy)
            # the derived plan rides the executable: memory planning
            # divides predicted bytes by each var's shard factor, and
            # captures/benches read the summary without re-deriving
            cp._sharding_plan = getattr(policy, "derived", None)
            return cp

        cp = self._cache[key] = _shared_executable(
            key + (tuple(d.id for d in self.mesh.devices.flat),
                   tuple(sorted(state_shapes.items()))) + policy_inputs,
            build, program, feed_specs, fetch_names, scope_names,
            origin=_RUN_PARALLEL.name,
            why={"scope_signature": scope_names, "flags": key[5],
                 "device": "mesh:%s" % (mesh_sig,), "mode": "gspmd"},
            extra=("gspmd", mesh_sig) + policy_inputs)
        # built here or adopted: the executable carries the plan it
        # compiled with, and this instance takes it as its active plan
        self._active_plan = cp._sharding_plan
        return cp

    def compiled_text(self):
        """Optimized-HLO text of every sharded executable this executor
        has run (per-device shapes, collectives and kernels as the
        compiler left them) — see ``CompiledProgram.compiled_text``."""
        return [cp.compiled_text() for cp in self._cache.values()]

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else (feed_dict or {})
        if not self._pipeline_stages:
            return _run_step(self, _RUN_PARALLEL, self._program, feed,
                             fetch_list, self._scope, None, return_numpy)
        # the pipeline keeps its state packed per stage and its own jitted
        # callable: outside the step's core, under the same forensics
        # shell — a multichip step that never returns is THE hang the
        # watchdog exists for
        with _blackbox.guard(_RUN_PARALLEL.name):
            t0 = time.perf_counter()
            fetches = self._run_pipeline(fetch_list, feed, return_numpy)
            if _telemetry.ENABLED:
                # per-stage occupancy: the bubble fraction of the GPipe
                # schedule, one labeled series per stage
                _telemetry.record_pipeline_occupancy(
                    self._pipeline_stages, self._pipeline_micro)
                _telemetry.record_step(
                    "pipeline", time.perf_counter() - t0,
                    fingerprint=program_fingerprint(self._program))
            return fetches

    # -- what ``executor._run_step`` asks of its executor, for a mesh -------
    # with no device the base key stays on the host: the mesh executable
    # places it (replicated) with its other arguments
    _step_key = Executor._step_key

    def _prepare_feeds(self, program, feed, device=None):
        if isinstance(feed, list):
            # per-device feed dicts (fluid API) -> concat along batch.
            feed = {
                name: np.concatenate(
                    [np.asarray(d[name]) for d in feed], axis=0)
                for name in feed[0]
            }
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
        return feeds, feed_specs

    def _place_state(self, name, val, cp, device=None):
        """A scope value as the mesh's executable takes it. State
        initialized by the single-device startup Executor is committed to
        one device; donated jit args must already carry the mesh sharding,
        so reshard explicitly (BCastParamsToDevices role,
        parallel_executor.cc:180)."""
        if isinstance(val, jax.Array):
            return self._ensure_sharded(val, cp.shardings.state_sharding(name))
        return val

    def _book_plan(self, cp, program, feeds, feed_specs, fingerprint,
                   device=None):
        """HBM ledger: feeds/fetches (global sharded arrays) book under
        one 'mesh' label; STATE books per device from real shard sizes
        (``_book_state``), so the ledger shows each chip's param/opt_state
        residency under the derived plan."""
        _telemetry.record_device_transfer(
            self._feed_bytes_by_device(cp, feeds))
        if not getattr(cp, "_memory_plan_done", False):
            shard_factors = mesh_devices = None
            if cp._sharding_plan is not None:
                from paddle_tpu.parallel.sharding import plan_shard_factors

                shard_factors = plan_shard_factors(cp._sharding_plan)
                mesh_devices = self.device_count
            _memory.register_plan_for(cp, program, feed_specs, fingerprint,
                                      shard_factors=shard_factors,
                                      mesh_devices=mesh_devices)
        return "mesh"

    @staticmethod
    def _book_state(cp, program, new_state, mem_dev):
        # per-device ledger entries from the REAL shard sizes: a
        # param fsdp-sharded 4 ways books ~1/4 of its bytes on each
        # device label; replicated state books full bytes on every
        # device — paddle_tpu_hbm_live_bytes{device,kind} shows the
        # derived plan's memory win directly
        _memory.track_state_sharded(cp, program, new_state,
                                    fallback_device=mem_dev)

    @staticmethod
    def _device_times(fetches, new_state, t_dispatch):
        """Per-device dispatch->ready latency: the straggler/imbalance
        signal."""
        return _telemetry.device_step_times(
            list(fetches) + list(new_state.values()), t_dispatch)

    def _dispatch_fields(self, mode=None):
        return {"mesh": dict(self.mesh.shape)}

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

        fetch_names = _fetch_names(fetch_list)
        if self._loss_name and fetch_names and fetch_names != [
                self._loss_name]:
            raise ValueError(
                "pipeline runs fetch only the loss (%r), got %r — params "
                "live packed per stage; use pipeline_sync_scope() to "
                "inspect them" % (self._loss_name, fetch_names))
        feeds, feed_specs = self._prepare_feeds(self._program, feed)
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
        key = step_key_value(self._step_key(self._program, None))
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
