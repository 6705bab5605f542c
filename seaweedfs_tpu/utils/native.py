"""ctypes loader for the C++ host-side library (native/).

The native library accelerates host-path hot spots the way the reference
leans on Go-assembly SIMD (klauspost/crc32, klauspost/reedsolomon):
CRC32-C, GF(2^8) encode for the CPU coder, and needle scanning.
Pure-Python fallbacks exist for every entry point.

The shared object is a build product, not a tracked file: `load()`
runs `make -C native` (the Makefile owns the flags) when it is absent
or older than its source.  Without a compiler the build fails, that is
logged once with the compiler's error, and callers get None.
"""

from __future__ import annotations

import ctypes
import functools
import os
import subprocess

from . import glog

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_LIB = "libseaweed_native.so"
_SRC = "seaweed_native.cpp"


def _build(lib: str) -> None:
    """`make` into a private name, then rename into place: several
    processes may start at once, and none may dlopen a half-written
    file."""
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, "-s", f"TARGET={tmp}"],
                       check=True, capture_output=True, text=True,
                       timeout=300)
        os.replace(os.path.join(_NATIVE_DIR, tmp), lib)
    finally:
        try:
            os.unlink(os.path.join(_NATIVE_DIR, tmp))
        except FileNotFoundError:
            pass


@functools.lru_cache(maxsize=1)
def load() -> ctypes.CDLL | None:
    """The native library (its path is `load()._name`), built first if
    need be; None when it cannot be built or loaded."""
    override = os.environ.get("SEAWEEDFS_TPU_NATIVE_LIB")
    lib = override or os.path.join(_NATIVE_DIR, _LIB)
    src = os.path.join(_NATIVE_DIR, _SRC)
    if not override and os.path.exists(src) and (
            not os.path.exists(lib)
            or os.path.getmtime(lib) < os.path.getmtime(src)):
        try:
            _build(lib)
        except subprocess.CalledProcessError as e:
            glog.warningf("native library build failed (pure-Python "
                          "fallbacks in use):\n%s", e.stderr.strip())
        except (OSError, subprocess.TimeoutExpired) as e:
            glog.warningf("native library build could not run (pure-"
                          "Python fallbacks in use): %s", e)
    if not os.path.exists(lib):
        return None
    try:
        return ctypes.CDLL(lib)
    except OSError as e:
        glog.warningf("native library %s does not load: %s", lib, e)
        return None


def crc32c_fn(lib: ctypes.CDLL):
    """Wrap uint32 sw_crc32c(uint32 crc, const uint8* buf, size_t len)."""
    fn = lib.sw_crc32c
    fn.restype = ctypes.c_uint32
    fn.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]

    def crc32c(data: bytes, crc: int = 0) -> int:
        return fn(crc, bytes(data), len(data))

    return crc32c


def gf_encode_fn(lib: ctypes.CDLL):
    """Wrap the C++ GF(2^8) row-mix (CPU fallback coder).

    void sw_gf_mix(const uint8* mat, int rows, int cols,
                   const uint8* const* shards_in, uint8** shards_out,
                   size_t n)
    """
    fn = lib.sw_gf_mix
    fn.restype = None
    fn.argtypes = [ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
                   ctypes.POINTER(ctypes.c_void_p),
                   ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t]
    return fn


def pread_rows_fn(lib: ctypes.CDLL):
    """Wrap void sw_pread_rows(int n, const int* fds, const int64* offs,
    uint8* const* dsts, const int64* lens, int64* got): n reads, each
    of lens[i] bytes of fds[i] from offs[i] into a buffer of its own,
    behind one release of the interpreter's lock (ctypes drops it
    around a CDLL call, once, where n `os.preadv` calls drop and retake
    it n times — beside sixteen busy request threads each retake waits
    its turn)."""
    fn = lib.sw_pread_rows
    fn.restype = None
    fn.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                   ctypes.POINTER(ctypes.c_int64),
                   ctypes.POINTER(ctypes.c_void_p),
                   ctypes.POINTER(ctypes.c_int64),
                   ctypes.POINTER(ctypes.c_int64)]

    def pread_rows(fds: list[int], offs: list[int], rows) -> list[int]:
        """Fill each of `rows` (writable numpy uint8 vectors) from its
        file at its offset; the bytes each read got."""
        n = len(fds)
        got = (ctypes.c_int64 * n)()
        fn(n, (ctypes.c_int * n)(*fds), (ctypes.c_int64 * n)(*offs),
           (ctypes.c_void_p * n)(*[r.ctypes.data for r in rows]),
           (ctypes.c_int64 * n)(*[r.nbytes for r in rows]), got)
        return list(got)

    return pread_rows
