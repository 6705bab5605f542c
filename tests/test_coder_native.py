"""Native C++ coder + CRC must match the pure-Python oracles byte-for-byte.

Skipped when native/libseaweed_native.so hasn't been built.
"""

import numpy as np
import pytest

from seaweedfs_tpu.core.crc import _crc32c_py
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.utils import native as native_mod

pytestmark = pytest.mark.skipif(native_mod.load() is None,
                                reason="native library not built")


def test_native_crc_matches_python():
    lib = native_mod.load()
    fn = native_mod.crc32c_fn(lib)
    assert fn(b"123456789") == 0xE3069283
    rng = np.random.default_rng(0)
    for size in (0, 1, 7, 8, 9, 1000, 4096):
        data = rng.integers(0, 256, size).astype(np.uint8).tobytes()
        assert fn(data) == _crc32c_py(data), size
    # incremental
    data = rng.integers(0, 256, 1000).astype(np.uint8).tobytes()
    assert fn(data[500:], fn(data[:500])) == fn(data)


def test_native_coder_matches_numpy():
    from seaweedfs_tpu.ops.coder_native import NativeCoder
    nc, oc = NativeCoder(10, 4), NumpyCoder(10, 4)
    data = np.random.default_rng(1).integers(
        0, 256, (10, 12345)).astype(np.uint8)
    assert np.array_equal(nc.encode(data), oc.encode(data))
    shards = oc.encode_all(data)
    lost = (1, 6, 10, 13)
    have = {i: shards[i] for i in range(14) if i not in lost}
    rec = nc.reconstruct(have)
    for sid in lost:
        assert np.array_equal(rec[sid], shards[sid])
    assert nc.verify(shards)


def test_native_alt_scheme():
    from seaweedfs_tpu.ops.coder_native import NativeCoder
    nc, oc = NativeCoder(8, 3), NumpyCoder(8, 3)
    data = np.random.default_rng(2).integers(
        0, 256, (8, 4096)).astype(np.uint8)
    assert np.array_equal(nc.encode(data), oc.encode(data))


def test_native_sanitizer_harness():
    """SURVEY §5: sanitizer builds for the C++ host kernels.  Builds
    the standalone harness with -fsanitize=address,undefined and runs
    it (crc vectors, gf_mul_add/gf_mix vs scalar reference at
    tail-stressing lengths)."""
    import os
    import shutil
    import subprocess
    if shutil.which("g++") is None:
        pytest.skip("g++ not available")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(["make", "asan-test"],
                         cwd=os.path.join(root, "native"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "native sanitizer harness OK" in out.stdout


# -- sw_pread_rows: a GET's shard reads in one call (ec/volume.py) --------

@pytest.fixture
def two_files(tmp_path):
    rng = np.random.default_rng(3)
    blobs = [rng.integers(0, 256, n).astype(np.uint8).tobytes()
             for n in (10000, 4096)]
    files = []
    for i, blob in enumerate(blobs):
        (tmp_path / f"f{i}").write_bytes(blob)
        files.append(open(tmp_path / f"f{i}", "rb"))
    yield files, blobs
    for f in files:
        f.close()


def _rows(*sizes):
    return [np.full(n, 0xAA, dtype=np.uint8) for n in sizes]


@pytest.mark.parametrize("reads", [
    [(0, 0, 10000)],                            # a single read, a whole file
    [(0, 0, 1), (0, 9999, 1), (1, 4095, 1)],    # first and last bytes
    [(0, 123, 4096), (1, 0, 4096), (0, 5000, 5000), (1, 7, 4000)],
    [(1, 4000, 96), (1, 4000, 97), (1, 4096, 8), (1, 9000, 8)],  # short
], ids=["single", "edges", "full", "short_at_the_end"])
def test_pread_rows_reads_what_preadv_reads(two_files, reads):
    import os
    files, _blobs = two_files
    fn = native_mod.pread_rows_fn(native_mod.load())
    mine, theirs = _rows(*(n for _f, _o, n in reads)), \
        _rows(*(n for _f, _o, n in reads))
    got = fn([files[f].fileno() for f, _o, _n in reads],
             [o for _f, o, _n in reads], mine)
    want = [os.preadv(files[f].fileno(), [row], o)
            for (f, o, _n), row in zip(reads, theirs)]
    assert got == want
    for a, b in zip(mine, theirs):      # the unread tail stays as it was
        assert np.array_equal(a, b)


def test_pread_rows_says_minus_one_for_a_read_that_fails(two_files):
    import os
    files, blobs = two_files
    gone = os.open(files[0].name, os.O_RDONLY)
    os.close(gone)
    fn = native_mod.pread_rows_fn(native_mod.load())
    rows = _rows(16, 16, 16)
    got = fn([files[0].fileno(), gone, files[1].fileno()], [0, 0, 32], rows)
    assert got == [16, -1, 16]          # the reads beside it still come
    assert rows[0].tobytes() == blobs[0][:16]
    assert rows[2].tobytes() == blobs[1][32:48]
    assert rows[1].tobytes() == b"\xaa" * 16


def test_read_many_goes_through_the_library_and_agrees_with_preadv(
        two_files, monkeypatch):
    from seaweedfs_tpu.ec import volume as ecv

    class Shard:
        def __init__(self, f):
            self._f = f
        fileno = lambda self: self._f.fileno()          # noqa: E731
        read_into = ecv.EcVolumeShard.read_into

    files, blobs = two_files
    shards = [Shard(f) for f in files]
    plan = [(0, 0, 4096), (1, 100, 3996), (1, 4000, 200), (0, 9999, 1)]
    assert ecv.read_many_path() == "sw_pread_rows"
    batched = _rows(*(n for _s, _o, n in plan))
    full = ecv.read_many([(shards[s], o, row)
                          for (s, o, _n), row in zip(plan, batched)])
    assert full == [True, True, False, True]
    # The same reads with the library taken away: a `preadv` a row.
    monkeypatch.setattr(ecv, "_pread_rows", lambda: None)
    assert ecv.read_many_path() == "preadv"
    single = _rows(*(n for _s, _o, n in plan))
    assert ecv.read_many([(shards[s], o, row) for (s, o, _n), row
                          in zip(plan, single)]) == full
    for a, b, (s, o, n) in zip(batched, single, plan):
        assert np.array_equal(a, b)
        got = blobs[s][o:o + n]
        assert a[:len(got)].tobytes() == got
