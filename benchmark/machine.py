"""The machine a run finds itself on: where its work directory goes and
how large one file there may be (copied from chip_smoke.py, PR 21 — the
chip check's machine caps the size of a file, and a volume is ONE file).

The work directory is made under `tempfile.gettempdir()`, which follows
TMPDIR: a run writes nowhere else outside its checkout.
"""

from __future__ import annotations

import errno
import os
import resource
import shutil
import tempfile

MIB = 1 << 20


class BenchFailure(Exception):
    """A run that may print no result line."""


def check(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def file_size_cap(directory: str, want: int) -> int:
    """The largest file, up to `want` bytes, that `directory` holds,
    within 1 MiB: RLIMIT_FSIZE or the file system's own limit, found by
    growing a sparse file (nothing is written)."""
    fd, path = tempfile.mkstemp(dir=directory, prefix="bench_cap_")
    try:
        def fits(n: int) -> bool:
            try:
                os.ftruncate(fd, n)
            except OSError as e:
                if e.errno != errno.EFBIG:
                    raise
                return False
            return True

        if fits(want):
            return want
        lo, hi = 0, want
        while hi - lo > MIB:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if fits(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)
        os.unlink(path)


def place_work_dir(want_file: int) -> tuple[str, dict]:
    """(a new work directory under the temp directory, what was found
    there: the file-size cap up to `want_file` and the free bytes)."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    parent = tempfile.gettempdir()
    found = {"work_parent": parent, "rlimit_fsize": [soft, hard],
             "file_cap": file_size_cap(parent, want_file),
             "free_bytes": shutil.disk_usage(parent).free}
    return tempfile.mkdtemp(prefix="bench_", dir=parent), found
