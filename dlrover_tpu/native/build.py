"""Build + load the native library.

JIT-compiles the C++ sources with g++ on first import and caches the .so
next to the sources, keyed by a hash of their contents and of the host
CPU's feature flags (the build uses ``-march=native``) — the same
compile-on-demand approach as the reference's op_builder
(atorch/atorch/ops/op_builder/builder.py), minus the CUDA toolchain.
"""

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
import threading

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["src/kv_store.cc", "src/sparse_optimizers.cc"]
_HEADERS = ["src/kv_store.h"]

_lock = threading.Lock()
_lib = None


def _host_cpu_flags() -> bytes:
    """What ``-march=native`` resolves to on this host: the CPU's
    feature flags. Part of the artifact key, because the tree — built
    artifacts included — gets copied between machines, and a library
    compiled for one CPU must not be loaded on another."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    return line
    except OSError:
        pass
    return platform.machine().encode()


def _source_hash(files=None) -> str:
    h = hashlib.sha256(_host_cpu_flags())
    for rel in (_SOURCES + _HEADERS if files is None else files):
        with open(os.path.join(_SRC_DIR, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _compile(srcs, out_path: str, extra_flags=()) -> None:
    """g++ the sources ATOMICALLY into out_path (temp file + rename, so
    concurrent builders race benignly and an interrupted build never
    leaves a truncated artifact at the cached path); retries without
    -march=native for toolchains that reject it."""
    fd, tmp = tempfile.mkstemp(
        suffix=os.path.splitext(out_path)[1] or ".tmp", dir=_SRC_DIR
    )
    os.close(fd)
    cmd = [
        "g++", "-O3", "-std=c++17", "-march=native",
        "-I", os.path.join(_SRC_DIR, "src"),
        *extra_flags, *srcs, "-o", tmp, "-lpthread",
    ]
    try:
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError:  # retry without -march
            cmd.remove("-march=native")
            try:
                subprocess.run(
                    cmd, check=True, capture_output=True, text=True
                )
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    "native build failed:\n"
                    f"$ {' '.join(cmd)}\n{e.stderr}"
                ) from e
        os.chmod(tmp, 0o755)
        os.replace(tmp, out_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_library() -> ctypes.CDLL:
    """Return the loaded native library, building it if needed."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so_path = os.path.join(_SRC_DIR, f"_dlrover_native_{_source_hash()}.so")
        if not os.path.exists(so_path):
            _compile(
                [os.path.join(_SRC_DIR, rel) for rel in _SOURCES],
                so_path,
                extra_flags=("-shared", "-fPIC"),
            )
        lib = ctypes.CDLL(so_path)
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    i64, i32, u32, u64, f32 = c.c_int64, c.c_int, c.c_uint32, c.c_uint64, c.c_float
    pi64 = c.POINTER(c.c_int64)
    pu32 = c.POINTER(c.c_uint32)
    pf32 = c.POINTER(c.c_float)

    lib.kv_create.restype = i64
    lib.kv_create.argtypes = [c.c_char_p, i32, i32, i32, u32]
    lib.kv_destroy.argtypes = [i64]
    lib.kv_set_init.argtypes = [i64, i32, f32, u64]
    lib.kv_size.restype = i64
    lib.kv_size.argtypes = [i64]
    for fn in ("kv_dim", "kv_width", "kv_n_slots"):
        getattr(lib, fn).restype = i32
        getattr(lib, fn).argtypes = [i64]
    lib.kv_gather_or_zeros.argtypes = [i64, pi64, i32, pf32]
    lib.kv_gather_or_insert.argtypes = [i64, pi64, i32, pf32, u32]
    lib.kv_gather_full.argtypes = [i64, pi64, i32, pf32, u32]
    lib.kv_insert.argtypes = [i64, pi64, i32, pf32, u32]
    lib.kv_scatter.argtypes = [i64, pi64, i32, pf32, i32, u32]
    lib.kv_get_frequency.argtypes = [i64, pi64, i32, pu32]
    lib.kv_get_timestamp.argtypes = [i64, pi64, i32, pu32]
    lib.kv_increase_count.argtypes = [i64, pi64, i32, u32]
    lib.kv_delete.restype = i64
    lib.kv_delete.argtypes = [i64, pi64, i32]
    lib.kv_delete_before_ts.restype = i64
    lib.kv_delete_before_ts.argtypes = [i64, u32]
    lib.kv_count_export.restype = i64
    lib.kv_count_export.argtypes = [i64, i32]
    lib.kv_export.restype = i64
    lib.kv_export.argtypes = [i64, i32, i32, pi64, pf32, pu32, pu32, i64]
    lib.kv_count_deleted.restype = i64
    lib.kv_count_deleted.argtypes = [i64]
    lib.kv_export_deleted.restype = i64
    lib.kv_export_deleted.argtypes = [i64, pi64, i64]
    lib.kv_import.argtypes = [i64, pi64, i64, pf32, pu32, pu32, i32, i32]
    lib.kv_opt_slots.restype = i32
    lib.kv_opt_slots.argtypes = [i32]
    lib.kv_sparse_apply.restype = i64
    lib.kv_sparse_apply.argtypes = [i64, i32, pi64, i32, pf32, pf32, u32]


def build_and_run_cc_tests(timeout_s: int = 120) -> str:
    """Compile + execute the native assert-based test binary
    (src/kv_store_test.cc — the reference's C++ suite analog,
    tfplus kv_variable_test.cc). Returns the binary's stdout; raises on
    compile failure, CHECK failure, or crash. Cached by source hash like
    the library build."""
    test_src = os.path.join(_SRC_DIR, "src", "kv_store_test.cc")
    # key by exactly the files the binary is built from
    digest = _source_hash(
        ["src/kv_store.cc", "src/kv_store.h", "src/kv_store_test.cc"]
    )
    exe = os.path.join(_SRC_DIR, f"_kv_store_test_{digest}")
    if not os.path.exists(exe):
        _compile(
            [os.path.join(_SRC_DIR, "src", "kv_store.cc"), test_src],
            exe,
        )
    out = subprocess.run(
        [exe], capture_output=True, text=True, timeout=timeout_s
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"native tests failed (rc={out.returncode}):\n"
            f"{out.stdout}{out.stderr}"
        )
    return out.stdout
