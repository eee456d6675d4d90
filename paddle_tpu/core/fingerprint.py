"""Structural program fingerprints: content-addressed executable cache keys.

The Executor used to key its executable cache on ``id(program)`` /
``id(scope)``. CPython reuses ``id()`` after GC, so a dead program's key
could alias a freshly-built program and serve a stale executable — and two
structurally identical programs (Predictor.Clone() threads, a re-built
bench program, a second Executor instance) could never share a compile.

``program_fingerprint`` walks every block in order and hashes the canonical
content that determines what the block lowers TO: op types, input/output
slot wiring, attrs, and the var symbol table specs (shape/dtype/lod/
persistable/stop_gradient) the lowerings and the feed-cast policy consult.
Runtime-only knobs (``random_seed`` feeds the step PRNG key, which is a
function *argument*) stay out. The digest is memoized per ``_version`` so
steady-state runs hash nothing; any graph surgery bumps ``_version``
(framework.py ``_bump_version``) and invalidates the memo.

``trace_flags_key`` joins it in every cache key: these flags are read at
trace time inside op lowerings, so toggling one must recompile rather than
reuse a stale executable.
"""

import hashlib

# Flags whose value changes what the block lowers TO (not just runtime
# behavior); they join the executable cache key so toggling recompiles.
# flash_backward is read inside the flash-attention custom_vjp at trace
# time. A choice made while a program is BUILT shows up in the program's
# structure and needs no entry here.
TRACE_FLAGS = ("use_pallas_lstm", "use_pallas_gru", "remat_gradients",
               "conv_nhwc", "attention_impl", "flash_backward",
               "paged_attention", "tree_attention")


def trace_flags_key():
    from paddle_tpu import flags

    return tuple((n, flags.get(n)) for n in TRACE_FLAGS)


def _encode(value, update):
    """Feed ``value`` into the hash as an unambiguous, type-tagged byte
    stream (so e.g. 1 vs True vs "1" vs 1.0 hash differently and list
    nesting cannot be confused with concatenation)."""
    if value is None:
        update(b"N")
    elif value is True:
        update(b"T")
    elif value is False:
        update(b"F")
    elif isinstance(value, int):
        update(b"i%d;" % value)
    elif isinstance(value, float):
        update(b"f")
        update(repr(value).encode())
        update(b";")
    elif isinstance(value, str):
        b = value.encode("utf-8", "surrogatepass")
        update(b"s%d:" % len(b))
        update(b)
    elif isinstance(value, bytes):
        update(b"b%d:" % len(value))
        update(value)
    elif isinstance(value, (list, tuple)):
        update(b"[")
        for item in value:
            _encode(item, update)
        update(b"]")
    elif isinstance(value, dict):
        update(b"{")
        for k in sorted(value, key=repr):
            _encode(k, update)
            update(b"=")
            _encode(value[k], update)
        update(b"}")
    elif isinstance(value, (set, frozenset)):
        update(b"<")
        for item in sorted(value, key=repr):
            _encode(item, update)
        update(b">")
    else:
        try:
            import numpy as np

            if isinstance(value, np.ndarray):
                update(b"a")
                _encode((str(value.dtype), value.shape), update)
                update(np.ascontiguousarray(value).tobytes())
                return
            if isinstance(value, np.generic):
                _encode(value.item(), update)
                return
        except ImportError:  # pragma: no cover
            pass
        # Last resort (enum-ish objects, Places...): repr is stable within
        # a process and across processes for value-like types.
        update(b"r")
        update(repr(value).encode("utf-8", "replace"))
        update(b";")


def _encode_var(name, v, update):
    _encode(
        (
            name,
            None if v.shape is None else tuple(v.shape),
            v.dtype,
            getattr(v, "lod_level", 0),
            bool(v.persistable),
            bool(getattr(v, "stop_gradient", False)),
            getattr(v, "type", None),
            bool(getattr(v, "is_data", False)),
        ),
        update,
    )


def _encode_op(op, update):
    _encode(op.type, update)
    _encode(
        sorted((slot, tuple(names)) for slot, names in op.inputs.items()),
        update,
    )
    _encode(
        sorted((slot, tuple(names)) for slot, names in op.outputs.items()),
        update,
    )
    _encode(op.attrs, update)


def program_fingerprint(program):
    """Canonical content hash (hex sha256) of a Program's structure.

    Memoized on ``program._version``: mutation through the framework API
    bumps the version and forces a re-hash; direct attribute pokes that
    bypass ``_bump_version`` are invisible here exactly as they were
    invisible to the reference's version-keyed program cache.
    """
    memo = getattr(program, "_fingerprint_memo", None)
    if memo is not None and memo[0] == program._version:
        return memo[1]
    h = hashlib.sha256()
    update = h.update
    _encode(
        (program._is_test, getattr(program, "_amp_dtype", None)), update
    )
    for block in program.blocks:
        _encode((block.idx, block.parent_idx), update)
        for name in sorted(block.vars):
            _encode_var(name, block.vars[name], update)
        for op in block.ops:
            _encode_op(op, update)
    digest = h.hexdigest()
    program._fingerprint_memo = (program._version, digest)
    return digest


def executable_key(program, feed_specs, fetch_names, scope_names, extra=()):
    """Stable cross-process digest for one executable: the structural
    fingerprint x feed specs x fetch set x scope signature x trace flags
    x caller extras (device platform/kind, steps, mesh...). The
    persistent exec cache (core/exec_cache.py) appends jax/jaxlib
    versions before this touches disk."""
    h = hashlib.sha256()
    update = h.update
    update(program_fingerprint(program).encode())
    _encode(
        tuple(sorted(
            (n, tuple(s), str(d)) for n, (s, d) in feed_specs.items()
        )),
        update,
    )
    _encode(tuple(fetch_names), update)
    _encode(tuple(sorted(scope_names)), update)
    _encode(trace_flags_key(), update)
    _encode(tuple(extra), update)
    return h.hexdigest()
