"""Build-on-demand ctypes loader for the native library."""

import ctypes
import os
import subprocess
import threading

_LIB = None
_LOCK = threading.Lock()
_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "_det_native.so")
SOURCES = ("hashmap.cpp", "io.cpp", "host_apply.cpp")
CXXFLAGS = ("-O3", "-std=c++17", "-fPIC", "-shared", "-Wall", "-pthread")


def load():
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        srcs = [os.path.join(_DIR, f) for f in SOURCES]
        if (not os.path.exists(_SO)
                or any(os.path.getmtime(s) > os.path.getmtime(_SO)
                       for s in srcs)):
            # build to a temp name + atomic rename: concurrent processes
            # (multi-process tests) must never dlopen a half-written .so.
            # A failed build raises: a stale library or a slower stand-in
            # would hide it. Keep native/Makefile's command equal to this.
            tmp = f"{_SO}.build.{os.getpid()}"
            cmd = ["g++", *CXXFLAGS, *srcs, "-o", tmp]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"building {_SO} failed ({' '.join(cmd)}):\n"
                    f"{e.stderr[-2000:]}") from e
            os.replace(tmp, _SO)
        lib = ctypes.CDLL(_SO)

        i64 = ctypes.c_int64
        p = ctypes.c_void_p
        lib.il_create.restype = p
        lib.il_create.argtypes = [i64]
        lib.il_destroy.argtypes = [p]
        lib.il_size.restype = i64
        lib.il_size.argtypes = [p]
        lib.il_lookup_or_insert.argtypes = [p, ctypes.c_void_p, i64, ctypes.c_void_p]
        lib.il_lookup.argtypes = [p, ctypes.c_void_p, i64, ctypes.c_void_p]
        lib.il_export_keys.argtypes = [p, ctypes.c_void_p]
        lib.il_export_counts.argtypes = [p, ctypes.c_void_p]
        # erase/free-slot surface (ISSUE 7)
        lib.il_erase.argtypes = [p, ctypes.c_void_p, i64, ctypes.c_void_p]
        lib.il_high_water.restype = i64
        lib.il_high_water.argtypes = [p]
        lib.il_free_count.restype = i64
        lib.il_free_count.argtypes = [p]
        lib.il_export_free.argtypes = [p, ctypes.c_void_p]

        lib.pf_create.restype = p
        lib.pf_create.argtypes = [ctypes.POINTER(ctypes.c_char_p), i64, i64]
        lib.pf_destroy.argtypes = [p]
        lib.pf_submit.restype = p
        lib.pf_submit.argtypes = [p, i64, i64, i64, ctypes.c_void_p]
        lib.pf_wait.argtypes = [p, p]
        lib.pf_read.restype = i64
        lib.pf_read.argtypes = [p, i64, i64, i64, ctypes.c_void_p]

        f32 = ctypes.c_float
        lib.ha_sgd.argtypes = [p, i64, p, p, p, i64, f32]
        lib.ha_adagrad.argtypes = [p, p, i64, p, p, p, i64, f32, f32]
        lib.ha_adam.argtypes = [p, p, p, i64, p, p, p, i64, f32, f32,
                                f32, f32, f32, f32]

        _LIB = lib
        return _LIB
