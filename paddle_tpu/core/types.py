"""Device places and variable types.

Reference parity: ``paddle/fluid/platform/place.h:25,36,51`` (Place variant)
and ``paddle/fluid/framework/framework.proto:105`` (VarType). On TPU the
device runtime is owned by JAX/PJRT, so a Place resolves to a ``jax.Device``
instead of carrying CUDA stream state.
"""

import os

import numpy as np


class NoAcceleratorError(RuntimeError):
    """The caller asked for the accelerator and JAX found none."""


def require_accelerator(count=1):
    """The accelerator or fail: the first ``count`` non-CPU devices this
    process owns, as JAX reports them. The entry points that MEASURE
    (``chip_smoke.py``, ``perfbench/run.py`` on a chip,
    ``tools/kernel_bench.py``) call this first, so a
    host with no chip is an error that names the missing device — never
    a CPU number under a device metric's name."""
    import jax

    devices = jax.local_devices()
    chips = [d for d in devices if d.platform != "cpu"]
    if len(chips) < count:
        raise NoAcceleratorError(
            "need %d accelerator device(s) but JAX found %d: "
            "jax.local_devices() is %s (default backend %r, "
            "JAX_PLATFORMS=%r)"
            % (count, len(chips),
               ["%s:%d" % (d.platform, d.id) for d in devices],
               jax.default_backend(),
               os.environ.get("JAX_PLATFORMS")))
    return chips[:count]


class Place(object):
    """Base device tag. Resolves lazily to a jax.Device: the
    ``device_id``-th device of the Place's platform that this process
    owns. An id beyond that pool is an error — ``TPUPlace(3)`` on a
    one-chip host is not chip 0."""

    _kind = None  # platform the pool is drawn from; None = default backend

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def _pool(self):
        import jax

        # Ask the backend for this platform directly: jax.devices() only
        # lists the DEFAULT platform, so with an accelerator present a
        # CPUPlace would otherwise resolve to the accelerator. Under
        # jax.distributed, jax.devices() is the GLOBAL list; an Executor
        # place must be a device this process owns.
        devs = jax.devices(self._kind) if self._kind else jax.devices()
        return [d for d in devs if d.process_index == jax.process_index()]

    def jax_device(self):
        pool = self._pool()
        if not 0 <= self.device_id < len(pool):
            raise ValueError(
                "%r is out of range: this process owns %d such "
                "device(s) (%s)" % (
                    self, len(pool),
                    ", ".join("%s:%d" % (d.platform, d.id) for d in pool)))
        return pool[self.device_id]

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class TPUPlace(Place):
    """The accelerator device tag — the ``CUDAPlace`` analog
    (place.h:36): the ``device_id``-th non-CPU device this process owns.

    On a host where JAX has NO accelerator (the forced-CPU test
    backend, ``JAX_PLATFORMS=cpu``) it resolves to the CPU devices
    instead, so that programs written for the chip run unchanged in
    tests. That makes ``TPUPlace()`` a statement of preference, not of
    fact: code that reports a device number must establish the device
    with ``require_accelerator()`` (or read ``jax_device().platform``),
    not infer it from the Place's name."""

    _kind = "tpu"

    def _pool(self):
        import jax

        devices = jax.local_devices()
        return [d for d in devices if d.platform != "cpu"] or devices


class CPUPlace(Place):
    _kind = "cpu"


class CUDAPlace(TPUPlace):
    """Porting-compat alias (place.h:36): there is no CUDA in this
    framework — a script's ``fluid.CUDAPlace(0)`` maps to the accelerator
    place (TPUPlace), with a one-time warning so the difference is
    visible."""

    _warned = False

    def __init__(self, device_id=0):
        super(CUDAPlace, self).__init__(device_id)
        if not CUDAPlace._warned:
            CUDAPlace._warned = True
            import warnings

            warnings.warn(
                "CUDAPlace maps to the TPU/accelerator place in "
                "paddle_tpu (no CUDA backend exists)", UserWarning,
                stacklevel=2)


class CUDAPinnedPlace(CPUPlace):
    """Porting-compat alias (place.h:51): pinned host memory is a CUDA
    transfer-staging concept; host arrays feed the accelerator directly
    here, so this is the CPU place."""

    def __init__(self, device_id=0):
        super(CUDAPinnedPlace, self).__init__(device_id)


class VarType(object):
    """Variable type tags (framework.proto:105 VarType.Type)."""

    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    STEP_SCOPES = "step_scopes"
    LOD_RANK_TABLE = "lod_rank_table"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    READER = "reader"
    RAW = "raw"
    # scalar data types live on Variable.dtype as canonical numpy names


_DTYPE_ALIASES = {
    "float": "float32",
    "double": "float64",
    "half": "float16",
    "bf16": "bfloat16",
    "int": "int32",
    "long": "int64",
    "bool_": "bool",
}

_SUPPORTED = (
    "float16",
    "bfloat16",
    "float32",
    "float64",
    "int8",
    "uint8",
    "int16",
    "int32",
    "int64",
    "bool",
)


def canonical_dtype(dtype):
    """Normalize any dtype spec (str/np.dtype/jnp dtype) to a canonical name."""
    if dtype is None:
        return "float32"
    if hasattr(dtype, "name"):
        name = dtype.name
    else:
        name = str(dtype)
    name = _DTYPE_ALIASES.get(name, name)
    if name not in _SUPPORTED:
        raise ValueError("unsupported dtype %r" % (dtype,))
    return name


def np_dtype(dtype):
    name = canonical_dtype(dtype)
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def is_float_dtype(dtype):
    return canonical_dtype(dtype) in ("float16", "bfloat16", "float32", "float64")


def core_version():
    return "paddle_tpu-core-0.1"


def device_dtype(dtype):
    """The dtype a value of `dtype` actually takes ON DEVICE: with jax
    x64 disabled (the TPU default), int64/uint64/float64 narrow to their
    32-bit forms. Lowerings request this directly instead of asking jnp
    for a width it will warn about and truncate anyway; host-side code
    (feeds, .npy persistence) keeps the declared width via np_dtype."""
    import jax.dtypes

    # the supported API for "what does this dtype canonicalize to on
    # device": narrows 64-bit widths iff x64 is off, tracking the flag
    # across jax versions
    return str(jax.dtypes.canonicalize_dtype(np_dtype(dtype)))
