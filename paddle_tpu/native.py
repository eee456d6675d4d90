"""ctypes binding to the native host runtime (native/libptpu_core.so).

Reference parity: the pybind layer role (paddle/fluid/pybind/pybind.cc) for
the host-side native components — recordio file IO, the blocking batch
queue, the C++ Scope, and the PTPB program IR parser. pybind11 is not in
the image, so the binding is a plain C API + ctypes (SURVEY.md §2.9 item
11). The library builds on demand with cmake+ninja (or a direct g++
fallback) and is cached under native/build/.

Usage:
    from paddle_tpu import native
    if native.available():
        q = native.NativeBlockingQueue(capacity=8)
        w = native.RecordIOWriter(path)
"""

import ctypes
import fcntl
import os
import subprocess
import threading

from paddle_tpu.observability import lock_witness

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libptpu_core.so")

_lib = None
_lib_lock = lock_witness.make_lock("native.lib")
_build_error = None


def _stale():
    """True when any native source is newer than the built library —
    the cmake path rebuilds incrementally anyway, but the bare-g++
    fallback (and a pre-built .so from an older checkout) would
    otherwise serve stale code silently."""
    try:
        lib_mtime = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    for sub in ("src", "include"):
        root = os.path.join(_NATIVE_DIR, sub)
        for dirpath, _, files in os.walk(root):
            for fn in files:
                try:
                    if os.path.getmtime(os.path.join(dirpath, fn)) \
                            > lib_mtime:
                        return True
                except OSError:
                    continue
    return False


def _build_library():
    """Compile libptpu_core.so (cmake+ninja, falling back to bare g++).

    Safe when several PROCESSES start it at once on a tree with no
    ``native/build/`` (six test workers importing this module do): an
    exclusive file lock around the build, and whoever waited for it finds
    the library built and goes away. The g++ fallback writes a name of
    its own and renames it, so no reader ever maps half a library."""
    build_dir = os.path.join(_NATIVE_DIR, "build")
    os.makedirs(build_dir, exist_ok=True)
    with open(os.path.join(build_dir, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)   # released when ``lock`` closes
        if not _stale():
            return   # another process built it while this one waited
        try:
            subprocess.run(
                ["cmake", "-S", _NATIVE_DIR, "-B", build_dir, "-G", "Ninja"],
                check=True, capture_output=True,
            )
            subprocess.run(
                ["cmake", "--build", build_dir], check=True,
                capture_output=True,
            )
            return
        except (OSError, subprocess.CalledProcessError):
            pass
        tmp_path = "%s.%d.tmp" % (_LIB_PATH, os.getpid())
        try:
            subprocess.run(
                [
                    "g++", "-std=c++17", "-O2", "-fPIC", "-shared",
                    "-pthread",
                    "-I", os.path.join(_NATIVE_DIR, "include"),
                    "-I", os.path.join(_NATIVE_DIR, "src"),
                    os.path.join(_NATIVE_DIR, "src", "c_api.cc"),
                    "-o", tmp_path,
                ],
                check=True, capture_output=True,
            )
            os.replace(tmp_path, _LIB_PATH)
        finally:
            if os.path.exists(tmp_path):
                os.remove(tmp_path)


def _declare(lib):
    c = ctypes
    P = c.c_void_p
    sigs = {
        "ptpu_last_error": ([], c.c_char_p),
        "ptpu_recordio_writer_open": ([c.c_char_p], P),
        "ptpu_recordio_write": ([P, c.c_void_p, c.c_uint64], c.c_int),
        "ptpu_recordio_writer_close": ([P], c.c_int),
        "ptpu_recordio_reader_open": ([c.c_char_p], P),
        "ptpu_recordio_next": ([P], c.c_int64),
        "ptpu_recordio_read": ([P, c.c_void_p, c.c_uint64], c.c_int),
        "ptpu_recordio_reader_close": ([P], c.c_int),
        "ptpu_queue_create": ([c.c_uint64], P),
        "ptpu_queue_push": ([P, c.c_void_p, c.c_uint64, c.c_int64], c.c_int),
        "ptpu_queue_pop": ([P, c.c_void_p, c.c_uint64, c.c_int64], c.c_int64),
        "ptpu_queue_size": ([P], c.c_uint64),
        "ptpu_queue_capacity": ([P], c.c_uint64),
        "ptpu_queue_close": ([P], None),
        "ptpu_queue_kill": ([P], None),
        "ptpu_queue_is_closed": ([P], c.c_int),
        "ptpu_queue_reopen": ([P], None),
        "ptpu_queue_destroy": ([P], None),
        "ptpu_scope_create": ([], P),
        "ptpu_scope_new_child": ([P], P),
        "ptpu_scope_set": (
            [P, c.c_char_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int32,
             c.c_void_p, c.c_uint64], c.c_int),
        "ptpu_scope_get_meta": (
            [P, c.c_char_p, c.c_char_p, c.c_uint64, c.POINTER(c.c_int64),
             c.POINTER(c.c_int32)], c.c_int64),
        "ptpu_scope_get_data": ([P, c.c_char_p, c.c_void_p, c.c_uint64],
                                c.c_int),
        "ptpu_scope_erase": ([P, c.c_char_p], c.c_int),
        "ptpu_scope_num_vars": ([P], c.c_uint64),
        "ptpu_scope_list": ([P, c.c_char_p, c.c_uint64], c.c_int64),
        "ptpu_scope_destroy": ([P], None),
        "ptpu_program_parse": ([c.c_void_p, c.c_uint64], P),
        "ptpu_program_num_blocks": ([P], c.c_int32),
        "ptpu_program_num_ops": ([P, c.c_int32], c.c_int32),
        "ptpu_program_num_vars": ([P, c.c_int32], c.c_int32),
        "ptpu_program_op_type": ([P, c.c_int32, c.c_int32, c.c_char_p,
                                  c.c_uint64], c.c_int64),
        "ptpu_program_serialize": ([P, c.c_void_p, c.c_uint64], c.c_int64),
        "ptpu_program_destroy": ([P], None),
        "ptpu_interp_run": ([P, P, c.c_int32], c.c_int),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype


def get_lib():
    """Load (building if needed) the native library; None if unbuildable.

    A failed stale-rebuild falls back to loading the existing library:
    stale-but-working beats none (e.g. a shipped prebuilt .so on a
    machine with no toolchain whose file mtimes got scrambled by the
    copy)."""
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None
        try:
            if not os.path.exists(_LIB_PATH):
                _build_library()
            elif _stale():
                try:
                    _build_library()
                except Exception:
                    pass  # keep serving the existing (stale) library
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
        except Exception as e:  # missing toolchain, RO filesystem, ...
            _build_error = e
            return None
        return _lib


def available():
    """True if the library is loadable, BUILDING it on first call if the
    toolchain is present (explicit opt-in path: tests, setup scripts)."""
    return get_lib() is not None


def prebuilt():
    """True only if libptpu_core.so is already built AND fresh — never
    triggers a compile. Hot paths (PyReader) use this so constructing a
    reader never stalls on a surprise cmake build. A STALE prebuilt lib
    returns False instead of being loaded: loading it would cache the
    stale handle into _lib and silently bypass the rebuild every later
    get_lib() would otherwise run (CDLL handles can't be reloaded
    in-process)."""
    if _lib is not None:
        return True
    if not os.path.exists(_LIB_PATH) or _stale():
        return False
    return get_lib() is not None  # fresh: no build can trigger


def last_error():
    lib = get_lib()
    return lib.ptpu_last_error().decode() if lib else str(_build_error)


class RecordIOWriter(object):
    """CRC32-framed record file writer (recordio capability)."""

    def __init__(self, path):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable: %s"
                               % _build_error)
        self._h = self._lib.ptpu_recordio_writer_open(path.encode())
        if not self._h:
            raise IOError(last_error())

    def write(self, data):
        data = bytes(data)
        rc = self._lib.ptpu_recordio_write(self._h, data, len(data))
        if rc != 0:
            raise IOError(last_error())

    def close(self):
        if self._h:
            self._lib.ptpu_recordio_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class RecordIOReader(object):
    """Iterator over a recordio file; raises IOError on corrupt records."""

    def __init__(self, path):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable: %s"
                               % _build_error)
        self._h = self._lib.ptpu_recordio_reader_open(path.encode())
        if not self._h:
            raise IOError(last_error())

    def __iter__(self):
        return self

    def __next__(self):
        n = self._lib.ptpu_recordio_next(self._h)
        if n == -1:
            raise StopIteration
        if n < 0:
            raise IOError(last_error())
        buf = ctypes.create_string_buffer(n)
        if self._lib.ptpu_recordio_read(self._h, buf, n) != 0:
            raise IOError(last_error())
        return buf.raw

    def close(self):
        if self._h:
            self._lib.ptpu_recordio_reader_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativeBlockingQueue(object):
    """C++-backed bounded byte queue (LoDTensorBlockingQueue role). Items
    are bytes; reader/py_reader layers serialize batches with numpy."""

    def __init__(self, capacity):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable: %s"
                               % _build_error)
        self._h = self._lib.ptpu_queue_create(capacity)
        self.capacity = capacity

    def push(self, data, timeout_ms=-1):
        data = bytes(data)
        rc = self._lib.ptpu_queue_push(self._h, data, len(data), timeout_ms)
        if rc == -2:
            raise TimeoutError("queue push timed out")
        return rc == 0

    def pop(self, timeout_ms=-1):
        """bytes, or None when the queue is closed and drained."""
        while True:
            n = self._lib.ptpu_queue_pop(self._h, None, 0, timeout_ms)
            if n == -2:
                raise TimeoutError("queue pop timed out")
            if n == 0:
                return None
            buf = ctypes.create_string_buffer(n)
            n2 = self._lib.ptpu_queue_pop(self._h, buf, n, timeout_ms)
            if n2 == 0:
                return None
            if n2 == -3:
                continue  # another consumer raced us; re-peek the new head
            if n2 == -2:
                raise TimeoutError("queue pop timed out")
            return buf.raw[:n2]

    def size(self):
        return self._lib.ptpu_queue_size(self._h)

    def close(self):
        self._lib.ptpu_queue_close(self._h)

    def kill(self):
        """Close AND discard queued items (abort semantics)."""
        self._lib.ptpu_queue_kill(self._h)

    def is_closed(self):
        return bool(self._lib.ptpu_queue_is_closed(self._h))

    def reopen(self):
        self._lib.ptpu_queue_reopen(self._h)

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.ptpu_queue_destroy(h)


class NativeScope(object):
    """C++ Scope holding named host ndarrays (Scope/Variable role)."""

    def __init__(self, _handle=None, _lib=None):
        self._lib = _lib or get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable: %s"
                               % _build_error)
        self._owned = _handle is None
        self._h = _handle or self._lib.ptpu_scope_create()

    def new_child(self):
        return NativeScope(
            _handle=self._lib.ptpu_scope_new_child(self._h), _lib=self._lib
        )

    def set(self, name, array):
        import numpy as np

        a = np.ascontiguousarray(array)
        dims = (ctypes.c_int64 * a.ndim)(*a.shape)
        rc = self._lib.ptpu_scope_set(
            self._h, name.encode(), str(a.dtype).encode(), dims, a.ndim,
            a.ctypes.data_as(ctypes.c_void_p), a.nbytes,
        )
        if rc != 0:
            raise RuntimeError(last_error())

    def get(self, name):
        """numpy array, or None if the var is absent (FindVar walk)."""
        import numpy as np

        dtype_buf = ctypes.create_string_buffer(32)
        dims = (ctypes.c_int64 * 16)()
        ndim = ctypes.c_int32()
        nbytes = self._lib.ptpu_scope_get_meta(
            self._h, name.encode(), dtype_buf, 32, dims, ctypes.byref(ndim)
        )
        if nbytes < 0:
            return None
        out = np.empty(
            tuple(dims[i] for i in range(ndim.value)),
            dtype=np.dtype(dtype_buf.value.decode()),
        )
        if nbytes:
            rc = self._lib.ptpu_scope_get_data(
                self._h, name.encode(),
                out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
            )
            if rc != 0:
                raise RuntimeError(last_error())
        return out

    def erase(self, name):
        return self._lib.ptpu_scope_erase(self._h, name.encode()) == 0

    def var_names(self):
        need = self._lib.ptpu_scope_list(self._h, None, 0)
        buf = ctypes.create_string_buffer(int(need))
        self._lib.ptpu_scope_list(self._h, buf, need)
        joined = buf.value.decode()
        return sorted(joined.split("\n")) if joined else []

    def __len__(self):
        return int(self._lib.ptpu_scope_num_vars(self._h))

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h and getattr(self, "_owned", False):
            self._lib.ptpu_scope_destroy(h)


def parse_program_bytes(data):
    """Parse PTPB bytes in C++ and return (num_blocks, ops_per_block,
    reserialized_bytes) — used to lockstep-test against program_bin.py."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable: %s" % _build_error)
    data = bytes(data)
    h = lib.ptpu_program_parse(data, len(data))
    if not h:
        raise ValueError(last_error())
    try:
        nblocks = lib.ptpu_program_num_blocks(h)
        ops = [lib.ptpu_program_num_ops(h, b) for b in range(nblocks)]
        need = lib.ptpu_program_serialize(h, None, 0)
        buf = ctypes.create_string_buffer(int(need))
        lib.ptpu_program_serialize(h, buf, need)
        return nblocks, ops, buf.raw[:need]
    finally:
        lib.ptpu_program_destroy(h)
