"""ctypes bindings for the native runtime (libpaddle_tpu_rt.so).

The reference framework's runtime services are C++ (profiler
`platform/profiler.cc`, monitor `platform/monitor.cc`, flags
`platform/flags.cc`, nan/inf `framework/details/nan_inf_utils*.cc`, shm
transport `memory/allocation/mmap_allocator.cc`); this package builds and
binds the TPU-native C++ equivalents. The library is compiled on first import
(cached by source mtime); when no toolchain is present everything degrades to
pure-python fallbacks and `AVAILABLE` is False.
"""
import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "src", "pt_runtime.cc"),
         os.path.join(_HERE, "src", "ps_service.cc")]
_LIB = os.path.join(_HERE, "libpaddle_tpu_rt.so")

AVAILABLE = False
_lib = None
_build_err = None
_lock = threading.Lock()


def _src_digest():
    import hashlib
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _needs_build():
    """Cache keyed on a content hash of the source (stored in a sidecar
    file), never on mtimes: after a fresh clone mtimes are checkout order,
    and an unauditable stale/committed binary must not win over the
    reviewed source."""
    if not os.path.exists(_LIB):
        return True
    try:
        with open(_LIB + ".hash") as f:
            return f.read().strip() != _src_digest()
    except OSError:
        return True


def _build():
    import tempfile
    # per-process temp name: concurrent first imports (launched trainers)
    # must not race on one shared tmp path
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
            "-fvisibility=hidden", "-o", tmp, *_SRCS, "-lrt",
        ]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.chmod(tmp, 0o755)  # mkstemp creates 0600; the lib must be
        os.replace(tmp, _LIB)  # readable by other users of the install
        with open(_LIB + ".hash", "w") as f:
            f.write(_src_digest())
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _bind(lib):
    c = ctypes
    LL, I, CP, VP = c.c_longlong, c.c_int, c.c_char_p, c.c_void_p
    sigs = {
        "pt_flag_set": (None, [CP, CP]),
        "pt_flag_get": (I, [CP, CP, I]),
        "pt_flag_list": (I, [CP, I]),
        "pt_stat_add": (None, [CP, LL]),
        "pt_stat_max": (None, [CP, LL]),
        "pt_stat_get": (LL, [CP]),
        "pt_stat_reset": (None, [CP]),
        "pt_stat_list": (I, [CP, I]),
        "pt_prof_enable": (None, []),
        "pt_prof_disable": (None, []),
        "pt_prof_enabled": (I, []),
        "pt_prof_now_ns": (LL, []),
        "pt_prof_event": (None, [CP, CP, LL, LL, LL]),
        "pt_prof_clear": (None, []),
        "pt_prof_count": (LL, []),
        "pt_prof_export": (LL, [CP]),
        "pt_prof_summary": (I, [CP, I]),
        "pt_count_nonfinite_f32": (LL, [VP, LL]),
        "pt_count_nonfinite_f64": (LL, [VP, LL]),
        "pt_count_nonfinite_bf16": (LL, [VP, LL]),
        "pt_count_nonfinite_f16": (LL, [VP, LL]),
        "pt_ring_create": (VP, [CP, LL]),
        "pt_ring_open": (VP, [CP]),
        "pt_ring_write": (I, [VP, VP, LL, I]),
        "pt_ring_next_len": (LL, [VP, I]),
        "pt_ring_read": (LL, [VP, VP, LL]),
        "pt_ring_close_producer": (None, [VP]),
        "pt_ring_free": (None, [VP, I]),
        "pt_ring_used": (LL, [VP]),
        "pt_runtime_version": (I, []),
        # parameter-server service (ps_service.cc)
        "pt_ps_reset": (None, []),
        "pt_ps_add_dense": (None, [c.c_uint32, I, I, c.c_float, c.c_float,
                                   c.c_float, c.c_float]),
        "pt_ps_add_sparse": (None, [c.c_uint32, I, I, c.c_float, c.c_float,
                                    c.c_float, c.c_float, c.c_float,
                                    c.c_uint64]),
        "pt_ps_add_graph": (None, [c.c_uint32, I]),
        "pt_ps_sparse_spill": (None, [c.c_uint32, c.c_uint64, CP]),
        "pt_ps_start": (I, [I]),
        "pt_ps_stop": (None, []),
        "pt_ps_port": (I, []),
        "pt_ps_running": (I, []),
        "pt_ps_dup_requests": (LL, []),
        "pt_ps_stats_json": (I, [c.c_char_p, I]),
        "pt_ps_trace_json": (I, [c.c_char_p, I, I]),
    }
    for name, (res, args) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args
    return lib


def _load():
    global _lib, AVAILABLE, _build_err
    with _lock:
        if _lib is not None or _build_err is not None:
            return _lib
        try:
            if _needs_build():
                _build()
            _lib = _bind(ctypes.CDLL(_LIB))
            assert _lib.pt_runtime_version() == 1
            AVAILABLE = True
        except Exception as e:  # no toolchain / bad env → python fallbacks
            _build_err = e
            _lib = None
        return _lib


def lib():
    """The bound library, or None when the native build is unavailable."""
    return _load()


# Eagerly try the build so AVAILABLE is accurate right after import.
_load()
