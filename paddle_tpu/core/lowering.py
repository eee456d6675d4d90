"""Block -> JAX function compiler: the execution engine's core.

This replaces the reference's per-op interpreter loop
(``paddle/fluid/framework/executor.cc:392-404`` RunPreparedContext) with a
whole-program trace: every op's registered lowering rule is applied in
program order to a symbolic environment, producing ONE JAX function for the
whole block, which ``jax.jit`` compiles to a single fused XLA executable.
SSA-graph scheduling (``details/threaded_ssa_graph_executor.cc``) becomes
XLA's job; gradient ops re-trace forward rules under jax.vjp and XLA CSE
dedups the recompute.
"""

import contextlib
import threading

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.observability import lock_witness
from paddle_tpu.core import op_registry
from paddle_tpu.core.op_registry import LowerContext, normalize_outputs

# One Python frame per source location, process-wide. JAX strips
# locations from a module before it hashes or compares it, but NOT from
# the Mosaic kernels serialized inside it, and by default a location
# carries the ten innermost frames — so the same kernel traced from two
# call stacks is two different custom calls. Seen on the chip, both ways
# this engine depends on them being one: (1) the design above counts on
# XLA CSE to merge the forward that a gradient op re-traces with the
# forward pass's own — with ten frames every flash forward ran TWICE
# per step (36 forward calls for 18 attentions; one frame: 18, and the
# Transformer-base step went 185 -> 162 ms); (2) the persistent compile
# cache keyed one train step differently for Executor.run and
# Executor.compiled_text, and missed after an edit moved a caller's
# line. The kernel's own line stays; op_name metadata is unaffected.
jax.config.update("jax_traceback_in_locations_limit", 1)

# Ops the engine interprets itself rather than via registry lowerings.
_STRUCTURAL_OPS = ("feed", "fetch")


def _valid(names):
    return [n for n in names if n]


class BlockLowerer(object):
    """Traces the ops of one block over a name->value environment."""

    def __init__(self, program, block_idx=0, is_test=False):
        self.program = program
        self.block = program.block(block_idx)
        self.is_test = is_test
        self._reshard_names = None  # lazy: vars carrying a reshard_spec

    def analyze(self, scope_names, feed_names):
        """Classify variable usage for the compiled step signature.

        Returns (state_in, state_out):
          state_in: persistable vars the block reads that must come from the
            scope (function inputs);
          state_out: persistable vars the block actually WRITES (function
            outputs, written back to the scope). Read-only state (inference
            params) stays out of state_out so CompiledProgram never donates
            its buffers — donation would invalidate scope arrays shared
            with concurrent runs.
        """
        defined = set(feed_names)
        state_in = []
        state_out = []
        seen_in = set()
        seen_out = set()
        for op, block in self._iter_ops_recursive(self.block):
            for name in _valid(op.input_arg_names()):
                if name in defined or name in seen_in:
                    continue
                v = block._find_var_recursive(name)
                if v is not None and v.persistable:
                    if name in scope_names:
                        seen_in.add(name)
                        state_in.append(name)
                    # else: must be produced earlier in the block or it is a
                    # genuine "not initialized" error surfaced at trace time.
            for name in _valid(op.output_arg_names()):
                defined.add(name)
                v = block._find_var_recursive(name)
                if v is not None and v.persistable and name not in seen_out:
                    seen_out.add(name)
                    state_out.append(name)
        return state_in, state_out

    def _iter_ops_recursive(self, block):
        for op in block.ops:
            yield op, block
            for attr in ("sub_block", "block", "true_block", "false_block"):
                idx = op.attrs.get(attr)
                if isinstance(idx, int) and 0 <= idx < self.program.num_blocks:
                    sub = self.program.block(idx)
                    for item in self._iter_ops_recursive(sub):
                        yield item

    def lower_into(self, env, step_key):
        """Run every op's lowering against env (name -> traced value)."""
        for op in self.block.ops:
            self.lower_op(op, env, step_key)
        return env

    def lower_op(self, op, env, step_key):
        if op.type in _STRUCTURAL_OPS:
            return
        opdef = op_registry.get_op_def(op.type)
        ins = {}
        for slot in opdef.input_slots():
            names = op.input(slot)
            if names:
                try:
                    if slot.endswith("@GRAD"):
                        # Grad slots keep positional alignment with their
                        # forward outputs: a hole (no incoming grad for that
                        # output) is None, not dropped.
                        ins[slot] = [env[n] if n else None for n in names]
                    else:
                        ins[slot] = [env[n] for n in _valid(names)]
                except KeyError as e:
                    raise RuntimeError(
                        "op %s reads uninitialized variable %s "
                        "(not fed, not persistable-in-scope, not produced "
                        "earlier in the block)" % (op.type, e)
                    )
        amp = getattr(self.program, "_amp_dtype", None)
        if amp:
            from paddle_tpu.core.amp import apply_amp_casts

            ins = apply_amp_casts(op.type, ins, amp)
        ctx = LowerContext(
            op,
            rng=_make_rng(step_key, op.attrs),
            is_test=self.is_test or op.attrs.get("is_test", False),
            block_lowerer=self,
        )
        # built under ``fluid.name_scope``: the instructions this op lowers
        # to carry the scope in their ``op_name``
        scope = op.attrs.get("op_namescope")
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            outs = normalize_outputs(opdef, opdef.lower(ctx, ins, op.attrs))
        for slot, arrs in outs.items():
            names = op.output(slot)
            for name, val in zip(names, arrs):
                if name and val is not None:
                    env[name] = self._apply_reshard(name, val)

    def _apply_reshard(self, name, val):
        """Explicit resharding point: a var the sharding transpiler
        (parallel/sharding.py) marked with ``reshard_spec`` — a
        tp-partial activation flowing into an op with no tp story — gets
        a ``with_sharding_constraint`` at its producer, so the conflict
        resolves as ONE visible collective instead of silent replication
        of the producing weight. Applies only under a mesh compile whose
        axes cover the spec (a later single-device or legacy-mesh compile
        of the same annotated program is untouched)."""
        names = self._reshard_names
        if names is None:
            # one sweep over the block chain; the common (unannotated)
            # case then skips the per-output recursive var lookup
            names = set()
            b = self.block
            while b is not None:
                for n, bv in b.vars.items():
                    if getattr(bv, "reshard_spec", None) is not None:
                        names.add(n)
                b = b.parent_block
            self._reshard_names = names
        if name not in names:
            return val
        v = self.block._find_var_recursive(name)
        spec = getattr(v, "reshard_spec", None)
        if spec is None:
            return val
        mesh = ambient_mesh()
        if mesh is None:
            return val
        axes = set()
        for entry in spec:
            if isinstance(entry, str):
                axes.add(entry)
            elif entry is not None:
                axes.update(entry)
        if not axes.issubset(set(mesh.shape)):
            return val
        from jax.sharding import NamedSharding, PartitionSpec

        try:
            return jax.lax.with_sharding_constraint(
                val, NamedSharding(mesh, PartitionSpec(*spec)))
        except Exception:
            # rank drift between annotation and trace (reshaped program):
            # the constraint is an optimization hint, never a hard failure
            return val

    def lower_sub_block(self, block_idx, env, step_key):
        """Lower a nested block (control-flow mega-ops) in-place on env."""
        sub = BlockLowerer(self.program, block_idx, is_test=self.is_test)
        for op in sub.block.ops:
            sub.lower_op(op, env, step_key)
        return env


def _make_rng(step_key, attrs):
    rng_id = attrs.get("__rng_id__", 0)
    seed = attrs.get("seed", 0)

    def rng():
        if seed:
            # Fixed-seed ops (fix_seed semantics): same stream every step.
            return jax.random.fold_in(jax.random.PRNGKey(seed), rng_id)
        return jax.random.fold_in(step_key, rng_id)

    return rng


# -- the step key --------------------------------------------------------------
# A run's key is fold_in(PRNGKey(seed), run counter). Made eagerly that is
# seven primitive binds, each a Python dispatch and a tiny executable of
# its own; so the executors hand the step's executable the two halves,
# ``(base_key(seed), np.uint32(counter))``, and the fold is traced into
# it (``fold_step_key``). Same primitives on the same integers: the bits
# are those of the eager key.
_base_keys = {}


def base_key(seed, device=None):
    """``jax.random.PRNGKey(seed)``, made once per (seed, device) and
    kept: on ``device``, or as a host array where the caller's executable
    places its own arguments (a mesh)."""
    k = (seed, device)
    key = _base_keys.get(k)
    if key is None:
        if len(_base_keys) >= 256:   # seeds are few; never grow unbounded
            _base_keys.clear()
        key = jax.random.PRNGKey(seed)
        key = (np.asarray(key) if device is None
               else jax.device_put(key, device))
        _base_keys[k] = key
    return key


def step_key(seed, counter, device=None):
    """The key of run ``counter`` under ``seed``, as a step's executable
    takes it: both executors make it here, so they cannot drift."""
    return base_key(seed, device), np.uint32(counter)


def fold_step_key(key):
    """(base key, run counter) -> the run's key. Traced into the step's
    executable."""
    base, counter = key
    return jax.random.fold_in(base, counter)


# the run's key as a value, in ONE dispatch: for whoever cannot take the
# two halves (the pipeline's executable, the NaN replay)
step_key_value = jax.jit(fold_step_key)


_AMBIENT_MESH = []  # trace-time stack: the mesh a sharded compile runs under
_AMBIENT_PLATFORM = []  # trace-time stack: platform the compile targets


def ambient_mesh():
    """The jax.sharding.Mesh of the ParallelExecutor compile currently
    being traced, or None. Lets op lowerings opt into mesh-aware forms
    (e.g. scaled_dot_product_attention's seq_parallel_axis routing to
    ring attention) without plumbing the mesh through every rule."""
    return _AMBIENT_MESH[-1] if _AMBIENT_MESH else None


def ambient_platform():
    """The platform ('cpu', 'tpu', ...) of the device the compile being
    traced is pinned to, or None when unpinned. Pallas kernel entry
    points use this to pick interpret mode: with several backends loaded
    (a TPU + CPU), ``jax.default_backend()`` names the
    highest-priority platform, NOT the Place this executable targets."""
    return _AMBIENT_PLATFORM[-1] if _AMBIENT_PLATFORM else None


def target_platform():
    """Platform the enclosing compile targets: the executor's pinned
    Place when lowering a program, else the process default backend."""
    plat = ambient_platform()
    if plat is not None:
        return plat
    return jax.default_backend()


def is_tpu_target():
    """True when the enclosing compile targets a non-CPU backend —
    the signal Pallas kernel entry points key interpret mode on."""
    return target_platform() not in ("cpu",)


def build_step_fn(program, feed_names, fetch_names, state_in, state_out,
                  is_test=False, mesh=None, platform=None):
    """Build the pure step function: (state, feeds, key) -> (new_state, fetches)."""
    lowerer = BlockLowerer(program, 0, is_test=is_test)

    def step(state, feeds, key):
        env = {}
        env.update(state)
        env.update(feeds)
        _AMBIENT_MESH.append(mesh)
        _AMBIENT_PLATFORM.append(platform)
        try:
            lowerer.lower_into(env, key)
        finally:
            _AMBIENT_MESH.pop()
            _AMBIENT_PLATFORM.pop()
        new_state = {}
        for n in state_out:
            if n in env:
                new_state[n] = env[n]
        fetches = []
        for n in fetch_names:
            if n not in env:
                raise RuntimeError(
                    "fetch variable %r was not produced by the program" % n
                )
            fetches.append(env[n])
        return new_state, fetches

    return step


class _LazyExecutable(object):
    """First-call executable resolution through the persistent cache
    (core/exec_cache.py): an AOT image on a warm start, a fresh (then
    serialized) compile otherwise. The executor stamps _exec_cache_key
    after construction; None keeps the plain jit path. Locked: the
    process-global registry shares one instance across serving threads,
    and two concurrent first calls must not both pay the compile."""

    def _init_lazy_exec(self):
        self._exec = None
        self._exec_cache_key = None
        self._arg_specs = None
        self._exec_lock = lock_witness.make_lock("core.lowering.exec")

    def compiled_text(self):
        """The optimized HLO the backend compiled for the argument
        shapes this executable first ran with: what a caller reads to
        prove which kernels (``tpu_custom_call``) and collectives are
        really in the program, rather than inferring it from a flag.
        Lowers and compiles again — a disk load where JAX's persistent
        cache is on."""
        if self._arg_specs is None:
            raise RuntimeError(
                "compiled_text(): this executable has not run yet")
        return self.jitted.lower(*self._arg_specs).compile().as_text()

    def _resolve_exec(self, args):
        fn = self._exec
        if fn is None:
            with self._exec_lock:
                fn = self._exec
                if fn is None:
                    import time as _time

                    from paddle_tpu import profiler
                    from paddle_tpu.core import exec_cache
                    from paddle_tpu.observability import watchdog

                    t0 = _time.perf_counter()
                    # a fresh compile can legitimately run minutes while
                    # the watchdog's step-derived timeout is seconds —
                    # slow-but-alive host work must not read as a hang
                    with watchdog.suspend():
                        fn = exec_cache.prepare_executable(
                            self.jitted, args, self._exec_cache_key
                        )
                    # first-call resolution (AOT deserialize or lower+
                    # compile+serialize) in the unified trace; the inner
                    # backend compile appears as its own span via the
                    # jax.monitoring taps
                    profiler.record_span(
                        "executable_resolve", t0, _time.perf_counter(),
                        cat="compile")
                    # shapes only: the jit's own in_shardings pin
                    # the devices
                    self._arg_specs = jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        args)
                    self._exec = fn
        return fn


def _target_platform_of(device, shardings):
    """Platform of the device(s) an executable is pinned to — the
    Place's device, else the sharding policy's mesh — or None when it
    is pinned to neither."""
    if device is not None:
        return device.platform
    if shardings is not None:
        return shardings.mesh.devices.flat[0].platform
    return None


class CompiledProgram(_LazyExecutable):
    """One jitted executable for a (program-version, shapes, fetches) key.

    With ``shardings`` (a ShardingPolicy from paddle_tpu.parallel), the jit
    runs under GSPMD over the policy's mesh: state/feed in_shardings are
    taken from the policy and XLA inserts the collectives — the
    ParallelExecutor/MultiDevSSAGraphBuilder capability without building
    per-device SSA graphs.
    """

    def __init__(
        self,
        program,
        feed_specs,
        fetch_names,
        scope_names,
        is_test=False,
        shardings=None,
        device=None,
    ):
        self.fetch_names = list(fetch_names)
        lowerer = BlockLowerer(program, 0, is_test=is_test)
        self.state_in, self.state_out = lowerer.analyze(
            scope_names, set(feed_specs)
        )
        self.step = build_step_fn(
            program,
            list(feed_specs),
            self.fetch_names,
            self.state_in,
            self.state_out,
            is_test=is_test,
            mesh=shardings.mesh if shardings is not None else None,
            platform=_target_platform_of(device, shardings),
        )
        # Donate ONLY state the program replaces (optimizer updates, BN
        # stats). Donating untouched state (e.g. params in an inference
        # program) would invalidate the scope's live buffers on backends
        # with real donation — a use-after-free for any later run or a
        # concurrent clone sharing the scope.
        self.mutable_state = sorted(set(self.state_in) & set(self.state_out))
        self.frozen_state = sorted(set(self.state_in) - set(self.state_out))
        step = self.step

        def split_step(mut_state, frozen_state, feeds, key):
            state = dict(frozen_state)
            state.update(mut_state)
            return step(state, feeds, fold_step_key(key))

        self.shardings = shardings
        self._init_lazy_exec()
        if shardings is None:
            if device is not None:
                # Pin the executable to the Place's device: with multiple
                # backends loaded (e.g. the TPU plugin + CPU), jit would
                # otherwise follow the default platform, not the Place.
                s = jax.sharding.SingleDeviceSharding(device)
                self.jitted = jax.jit(
                    split_step, donate_argnums=(0,), in_shardings=s,
                    out_shardings=s,
                )
            else:
                self.jitted = jax.jit(split_step, donate_argnums=(0,))
        else:
            mut_s = {n: shardings.state_sharding(n)
                     for n in self.mutable_state}
            frz_s = {n: shardings.state_sharding(n)
                     for n in self.frozen_state}
            feed_s = {
                n: shardings.feed_sharding(n, shape=feed_specs[n][0])
                for n in feed_specs
            }
            state_out_s = {n: shardings.state_sharding(n) for n in self.state_out}
            self.jitted = jax.jit(
                split_step,
                in_shardings=(mut_s, frz_s, feed_s, shardings.replicated()),
                out_shardings=(state_out_s, None),
                donate_argnums=(0,),
            )

    def __call__(self, state, feeds, key):
        """``key`` is the pair ``(base_key(seed), np.uint32(counter))``."""
        mut = {n: state[n] for n in self.mutable_state}
        frz = {n: state[n] for n in self.frozen_state}
        fn = self._resolve_exec((mut, frz, feeds, key))
        return fn(mut, frz, feeds, key)


class MultiStepProgram(_LazyExecutable):
    """K training steps compiled into ONE XLA executable via lax.scan.

    SURVEY §7 hard part (c): per-step Python dispatch costs a host round
    trip per step (nonzero everywhere). Scanning
    the step function amortizes dispatch to one call per K steps; state
    chains on device through the scan carry, and per-step fetches come
    back stacked [K, ...] (the loss curve, not just the last value).

    Feeds are constant across the K steps (synthetic-input benches) — real
    input pipelines should use the in-graph reader ops instead, which need
    no feeds at all. Requires state_out ⊆ state_in (training programs
    satisfy this: optimizer/BN state is read-modify-write).
    """

    def __init__(self, program, steps, feed_specs, fetch_names, scope_names,
                 is_test=False, device=None, stack_fetches=False):
        self.steps = int(steps)
        if self.steps <= 0:
            raise ValueError("multi-step needs steps >= 1, got %d" % steps)
        self.fetch_names = list(fetch_names)
        lowerer = BlockLowerer(program, 0, is_test=is_test)
        self.state_in, self.state_out = lowerer.analyze(
            scope_names, set(feed_specs)
        )
        extra_out = set(self.state_out) - set(self.state_in)
        if extra_out:
            raise RuntimeError(
                "multi-step compilation needs state_out ⊆ state_in; program "
                "creates persistables mid-run: %s" % sorted(extra_out)
            )
        step = build_step_fn(
            program, list(feed_specs), self.fetch_names,
            self.state_in, self.state_out, is_test=is_test,
            platform=getattr(device, "platform", None),
        )
        self.mutable_state = sorted(
            set(self.state_in) & set(self.state_out))
        self.frozen_state = sorted(
            set(self.state_in) - set(self.state_out))
        n_steps = self.steps

        def multi(mut_state, frozen_state, feeds, key):
            import jax.numpy as jnp

            key = fold_step_key(key)

            def body(carry, i):
                state = dict(frozen_state)
                state.update(carry)
                new_state, fetches = step(
                    state, feeds, jax.random.fold_in(key, i)
                )
                carry = {n: new_state[n] for n in carry}
                return carry, tuple(fetches)

            if stack_fetches:
                # per-step fetch trajectory [K, ...] — costs scan-output
                # buffers every iteration; use for small diagnostics only
                carry, ys = jax.lax.scan(
                    body, mut_state, jnp.arange(n_steps)
                )
                return carry, list(ys)

            # default: fetches from the LAST step ride the carry — no
            # per-iteration output buffers in the scan
            def body_carry(carry, i):
                st, _ = carry
                st2, fetches = body(st, i)
                return (st2, tuple(fetches)), None

            _, fetch0 = jax.eval_shape(
                lambda c: body(c, jnp.asarray(0)), mut_state
            )
            init_f = tuple(
                jnp.zeros(f.shape, f.dtype) for f in fetch0
            )
            (carry, fetches), _ = jax.lax.scan(
                body_carry, (mut_state, init_f), jnp.arange(n_steps)
            )
            return carry, list(fetches)

        if device is not None:
            s = jax.sharding.SingleDeviceSharding(device)
            self.jitted = jax.jit(
                multi, donate_argnums=(0,), in_shardings=s, out_shardings=s
            )
        else:
            self.jitted = jax.jit(multi, donate_argnums=(0,))
        self._init_lazy_exec()

    def __call__(self, state, feeds, key):
        mut = {n: state[n] for n in self.mutable_state}
        frz = {n: state[n] for n in self.frozen_state}
        fn = self._resolve_exec((mut, frz, feeds, key))
        return fn(mut, frz, feeds, key)
