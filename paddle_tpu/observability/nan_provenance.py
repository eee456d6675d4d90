"""NaN provenance: blame the FIRST op that produced a non-finite value.

``FLAGS_check_nan_inf``'s on-device scan (executor.py:_nan_check_start)
says *that* a step went non-finite, cheaply — one fused reduction, one
[n] bool vector to the host. It cannot say *where*: by the time the scan
trips, the NaN has flowed through the whole step. The reference checked
every op's outputs every step (operator.cc:754) — exact but ruinously
slow under XLA, where per-op sync would defeat whole-program fusion.

This module gets exactness without the steady-state cost: when the scan
trips, the executor hands over the step's *inputs* (a pre-step snapshot
of the donated mutable state, the feeds, the PRNG key — the step function
is pure, so these reproduce it bit-for-bit) and the program is replayed
HERE, op by op, eagerly, through the same registry lowerings the compiled
step traced (core/lowering.py:BlockLowerer). After each op, its outputs
are pulled to the host and checked; the first op with a non-finite output
while all its inputs were finite is the culprit. The finding is a
PR 3 :class:`analysis.diagnostics.Diagnostic` — rule ``N001``, severity
error, block/op location, involved vars, and a fix hint keyed on the op
type — so tools, tests and the black box consume it structurally.

Cost model: zero until a trip (the snapshot is one device-side copy of
the mutable state per step, only while ``FLAGS_check_nan_inf`` is on);
the replay itself is a per-op interpreter pass over one step — seconds,
paid once, on the way to an exception that was going to kill the job
anyway.
"""

import numpy as np

__all__ = ["NonFiniteError", "blame_step", "blame_multi_step",
           "RULE", "RULE_NAME"]

RULE = "N001"
RULE_NAME = "non-finite-output"

# op type -> one actionable sentence (the Diagnostic hint)
_HINTS = {
    "log": "log of a non-positive input — clip the input away from zero "
           "(e.g. x = clip(x, eps, inf)) or use a fused numerically-stable "
           "composite",
    "sqrt": "sqrt of a negative input — clip or square-then-sqrt",
    "rsqrt": "rsqrt of a non-positive input — add an epsilon inside the "
             "root (rsqrt(x + eps))",
    "elementwise_div": "division by zero — add an epsilon to the "
                       "denominator",
    "divide": "division by zero — add an epsilon to the denominator",
    "exp": "exp overflow — rescale the input or compute in log-space",
    "pow": "pow produced inf/nan — check for negative base with "
           "fractional exponent or overflow",
    "cross_entropy": "log(0) inside cross entropy — label-smooth or clip "
                     "the probabilities",
    "softmax_with_cross_entropy": "extreme logits — clip logits, lower "
                                  "the learning rate, or enable loss "
                                  "scaling under AMP",
    "fused_label_smooth_ce": "extreme logits — clip logits or lower the "
                             "learning rate",
}
_DEFAULT_HINT = ("inspect this op's inputs at the reported step; common "
                 "fixes: gradient clipping, a lower learning rate, epsilon "
                 "guards, or AMP loss scaling")


class NonFiniteError(RuntimeError):
    """The FLAGS_check_nan_inf error, upgraded with provenance: carries
    the structured :class:`Diagnostic` in ``.diagnostic`` (None when the
    replay could not localize the op). The message keeps the plain
    scanner's "NaN/Inf detected" prefix so existing handlers match."""

    def __init__(self, message, diagnostic=None):
        super(NonFiniteError, self).__init__(message)
        self.diagnostic = diagnostic


def _nonfinite_names(env, names):
    """The subset of ``names`` whose env value is a non-finite float
    array (host-syncs each checked value — replay-only path)."""
    bad = []
    for n in names:
        if not n or n not in env:
            continue
        try:
            arr = np.asarray(env[n])
        except Exception:
            continue
        if np.issubdtype(arr.dtype, np.floating) and not np.all(
                np.isfinite(arr)):
            bad.append(n)
    return bad


def _make_diagnostic(op_idx, op, bad_names, step_index=None):
    from paddle_tpu.analysis.diagnostics import Diagnostic

    where = ("" if step_index is None
             else " (step %d of the multi-step dispatch)" % step_index)
    return Diagnostic(
        RULE, RULE_NAME, "error",
        "op '%s' produced the first non-finite value%s in output(s) %s "
        "(all of its inputs were finite)"
        % (op.type, where, ", ".join(repr(n) for n in bad_names)),
        block_idx=0, op_idx=op_idx, op_type=op.type,
        var_names=tuple(bad_names),
        hint=_HINTS.get(op.type, _DEFAULT_HINT),
    )


def _input_diagnostic(bad_names, kind):
    from paddle_tpu.analysis.diagnostics import Diagnostic

    return Diagnostic(
        RULE, RULE_NAME, "error",
        "step %s already contained non-finite value(s) before any op ran: "
        "%s" % (kind, ", ".join(repr(n) for n in bad_names)),
        block_idx=0, var_names=tuple(bad_names),
        hint="the corruption happened upstream (a previous step's update "
             "or the input pipeline) — check the feed data and the prior "
             "step's optimizer update",
    )


def _replay(program, state, feeds, key, is_test, platform, step_index):
    """One eager op-by-op pass. Returns (diagnostic_or_None, final_env)."""
    from paddle_tpu.core.lowering import BlockLowerer, _AMBIENT_PLATFORM

    env = {}
    env.update(state)
    env.update(feeds)
    bad = _nonfinite_names(env, list(feeds))
    if bad:
        return _input_diagnostic(bad, "feeds"), env
    bad = _nonfinite_names(env, list(state))
    if bad:
        return _input_diagnostic(bad, "state"), env
    lowerer = BlockLowerer(program, 0, is_test=is_test)
    _AMBIENT_PLATFORM.append(platform)
    try:
        for idx, op in enumerate(lowerer.block.ops):
            lowerer.lower_op(op, env, key)
            bad = _nonfinite_names(env, op.output_arg_names())
            if bad:
                return _make_diagnostic(idx, op, bad,
                                        step_index=step_index), env
    finally:
        _AMBIENT_PLATFORM.pop()
    return None, env


def blame_step(program, state, feeds, key, is_test=False, platform=None,
               step_index=None):
    """Replay ONE step eagerly and localize the first non-finite output.

    ``state``/``feeds``/``key`` must be the step's actual inputs (the
    executor snapshots donated state before dispatch). Returns a
    Diagnostic, or None when the replay stays finite (e.g. the scan
    tripped on a value this block never touches). Never raises — a
    failed replay must not mask the original scanner error. Runs under
    ``watchdog.suspend()``: a minutes-long per-op replay on a big
    program is slow forensics, not a hang."""
    from paddle_tpu.observability import watchdog

    try:
        with watchdog.suspend():
            diag, _env = _replay(program, state, feeds, key, is_test,
                                 platform, step_index)
        return diag
    except Exception:
        return None


def blame_multi_step(program, state, feeds, key, steps, mutable_state,
                     is_test=False, platform=None):
    """Replay up to ``steps`` iterations of a run_multi_step dispatch
    (per-step key = fold_in(key, i) — ALSO for steps == 1, matching
    MultiStepProgram's scan body; mutable state chains between
    iterations) and blame the first non-finite op across them."""
    import jax

    from paddle_tpu.observability import watchdog

    state = dict(state)
    try:
        with watchdog.suspend():
            for i in range(int(steps)):
                step_key = jax.random.fold_in(key, i)
                diag, env = _replay(program, state, feeds, step_key,
                                    is_test, platform, step_index=i)
                if diag is not None:
                    return diag
                for n in mutable_state:
                    if n in env:
                        state[n] = env[n]
    except Exception:
        return None
    return None


def enrich_and_raise(base_exc, program, state, feeds, key, steps=1,
                     mutable_state=(), is_test=False, platform=None,
                     multi=False):
    """The executor's trip path: run the blame replay, file the finding
    with the black box + registry, and raise :class:`NonFiniteError`
    chained on the scanner's error. ``state`` is the pre-step snapshot
    (frozen state + copies of the donated mutable state). ``multi``
    marks a run_multi_step dispatch — the branch can't key on
    ``steps > 1`` because even steps == 1 runs through the scan body's
    ``fold_in(key, 0)``, and replaying with the raw key would diverge
    the RNG stream on programs with dropout/random ops."""
    from paddle_tpu.observability import blackbox
    from paddle_tpu.observability.metrics_registry import REGISTRY

    if multi:
        diag = blame_multi_step(program, state, feeds, key, steps,
                                mutable_state, is_test=is_test,
                                platform=platform)
    else:
        diag = blame_step(program, state, feeds, key, is_test=is_test,
                          platform=platform)
    REGISTRY.counter(
        "paddle_tpu_nan_trips_total",
        "FLAGS_check_nan_inf trips, by whether provenance localized them",
        labels=("blamed",),
    ).inc(blamed="yes" if diag is not None else "no")
    if diag is None:
        raise base_exc
    blackbox.record_nan_diagnostic(diag)
    if blackbox.ENABLED:
        blackbox.dump(reason="nan_diagnostic")
    raise NonFiniteError(
        "%s\n%s\n        hint: %s" % (str(base_exc), str(diag).split(
            "\n")[0], diag.hint),
        diagnostic=diag) from base_exc
