"""EC pipeline property tests — the port of the reference's ec_test.go.

Build a real volume, encode it with shrunken block sizes (large=10000,
small=100 — the reference test's constants), then assert:
- every needle read back through shard intervals equals the original;
- every needle reconstructs from shards even with 4 shard files deleted;
- rebuild regenerates missing shards byte-identically;
- decode (shards -> .dat) reproduces the original volume bytes;
- the deletion journal round-trips into idx tombstones.
"""

import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.core import idx as idx_mod
from seaweedfs_tpu.core import types as t
from seaweedfs_tpu.core.needle import Needle
from seaweedfs_tpu.ec import (DATA_SHARDS, TOTAL_SHARDS, to_ext)
from seaweedfs_tpu.ec.decoder import (find_dat_file_size,
                                      write_dat_file,
                                      write_idx_file_from_ec_index)
from seaweedfs_tpu.ec.encoder import (rebuild_ec_files,
                                      write_ec_files,
                                      write_sorted_file_from_idx)
from seaweedfs_tpu.ec.locate import locate_data
from seaweedfs_tpu.ec.shard_bits import ShardBits
from seaweedfs_tpu.ec.volume import (EcVolume, NeedleNotFound,
                                     ShardsUnavailable)
from seaweedfs_tpu.ops.erasure import new_coder
from seaweedfs_tpu.storage.volume import Volume

LARGE, SMALL = 10000, 100  # the reference test's shrunken block sizes


@pytest.fixture(scope="module")
def ec_base(tmp_path_factory):
    """A volume with ~120 random needles, encoded to shards."""
    tmp = tmp_path_factory.mktemp("ecvol")
    v = Volume(str(tmp), "", 1)
    rng = random.Random(42)
    payloads = {}
    for i in range(1, 121):
        data = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 800)))
        payloads[i] = data
        n = Needle(cookie=0x9999, id=i, data=data)
        n.append_at_ns = i  # deterministic
        v.write_needle(n)
    v.sync()
    base = v.file_name()
    v.close()
    write_sorted_file_from_idx(base)
    write_ec_files(base, coder=new_coder(backend="numpy"),
                   large_block_size=LARGE, small_block_size=SMALL,
                   chunk_size=SMALL)
    return base, payloads


def _open_ec(base, **kw):
    return EcVolume(base, coder=new_coder(backend="numpy"),
                    large_block_size=LARGE, small_block_size=SMALL, **kw)


def test_shard_files_created_and_sized(ec_base):
    base, _ = ec_base
    sizes = {os.path.getsize(base + to_ext(i)) for i in range(TOTAL_SHARDS)}
    assert len(sizes) == 1  # all equal
    size = sizes.pop()
    dat_size = os.path.getsize(base + ".dat")
    assert size * DATA_SHARDS >= dat_size
    assert size % SMALL == 0


def test_shard_rows_are_codewords(ec_base):
    """Every byte column across the 14 shard files is an RS codeword."""
    base, _ = ec_base
    shards = np.stack([
        np.frombuffer(open(base + to_ext(i), "rb").read(), dtype=np.uint8)
        for i in range(TOTAL_SHARDS)])
    assert new_coder(backend="numpy").verify(shards)


def test_every_needle_reads_back(ec_base):
    base, payloads = ec_base
    ev = _open_ec(base)
    try:
        for nid, data in payloads.items():
            n = ev.read_needle(nid)
            assert n.data == data, f"needle {nid}"
            assert n.cookie == 0x9999
    finally:
        ev.close()


def test_degraded_read_with_4_shards_lost(ec_base, tmp_path):
    """Copy shards, delete any 4, every needle must still read back
    (reconstruction from exactly 10 survivors) — readFromOtherEcFiles."""
    import shutil
    base, payloads = ec_base
    rng = random.Random(7)
    for trial in range(3):
        work = tmp_path / f"trial{trial}"
        work.mkdir()
        newbase = str(work / "1")
        for ext in [".ecx"] + [to_ext(i) for i in range(TOTAL_SHARDS)]:
            shutil.copyfile(base + ext, newbase + ext)
        # Trial 0 always loses shard 0: version detection must then
        # reconstruct the superblock from survivors instead of reading .ec00.
        lost = ([0] + rng.sample(range(1, TOTAL_SHARDS), 3)) if trial == 0 \
            else rng.sample(range(TOTAL_SHARDS), 4)
        for sid in lost:
            os.remove(newbase + to_ext(sid))
        ev = _open_ec(newbase)
        try:
            assert set(ev.shards) == set(range(TOTAL_SHARDS)) - set(lost)
            for nid, data in list(payloads.items())[::10]:
                assert ev.read_needle(nid).data == data, \
                    f"trial {trial} lost={lost} needle {nid}"
        finally:
            ev.close()


def test_rebuild_byte_identical(ec_base, tmp_path):
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    originals = {}
    for i in range(TOTAL_SHARDS):
        shutil.copyfile(base + to_ext(i), work + to_ext(i))
        originals[i] = open(base + to_ext(i), "rb").read()
    lost = [0, 5, 11, 13]
    for sid in lost:
        os.remove(work + to_ext(sid))
    generated = rebuild_ec_files(work, coder=new_coder(backend="numpy"),
                                 chunk_size=1000)
    assert sorted(generated) == lost
    for sid in lost:
        assert open(work + to_ext(sid), "rb").read() == originals[sid], sid


def test_rebuild_too_few_shards(ec_base, tmp_path):
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    for i in range(9):  # only 9 survivors
        shutil.copyfile(base + to_ext(i), work + to_ext(i))
    with pytest.raises(ValueError, match="too few"):
        rebuild_ec_files(work, coder=new_coder(backend="numpy"))


def test_decode_reproduces_dat(ec_base, tmp_path):
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    for ext in [".ecx"] + [to_ext(i) for i in range(DATA_SHARDS)]:
        shutil.copyfile(base + ext, work + ext)
    write_idx_file_from_ec_index(work)
    dat_size = find_dat_file_size(work)
    orig = open(base + ".dat", "rb").read()
    assert dat_size == len(orig)  # last record ends the file
    write_dat_file(work, dat_size, large_block_size=LARGE,
                   small_block_size=SMALL)
    assert open(work + ".dat", "rb").read() == orig
    # idx must match the original volume's live entries
    with open(work + ".idx", "rb") as f:
        entries = {e.key: e for e in idx_mod.iter_index(f)}
    with open(base + ".idx", "rb") as f:
        orig_entries = {e.key: e for e in idx_mod.iter_index(f)}
    assert entries == orig_entries


def test_ec_delete_journal(ec_base, tmp_path):
    import shutil
    base, payloads = ec_base
    work = str(tmp_path / "1")
    for ext in [".ecx"] + [to_ext(i) for i in range(TOTAL_SHARDS)]:
        shutil.copyfile(base + ext, work + ext)
    ev = _open_ec(work)
    try:
        ev.delete_needle(50)
        with pytest.raises(NeedleNotFound):
            ev.read_needle(50)
        ev.read_needle(51)  # neighbors unaffected
    finally:
        ev.close()
    # .ecj recorded the id; idx regeneration adds a tombstone.
    assert os.path.getsize(work + ".ecj") == 8
    write_idx_file_from_ec_index(work)
    with open(work + ".idx", "rb") as f:
        entries = list(idx_mod.iter_index(f))
    assert entries[-1].key == 50
    assert entries[-1].size == t.TOMBSTONE_FILE_SIZE


def test_the_index_is_one_mapping_searched_and_written(ec_base, tmp_path):
    """The sorted index has ONE path whatever its size: the mapping of
    the `.ecx` that a lookup searches is the one a delete writes its
    tombstone through, so the file, a second reader of it and a volume
    mounted later all see the delete at once."""
    import shutil
    base, payloads = ec_base
    work = str(tmp_path / "1")
    for ext in [".ecx"] + [to_ext(i) for i in range(TOTAL_SHARDS)]:
        shutil.copyfile(base + ext, work + ext)
    before = open(work + ".ecx", "rb").read()
    ev, other = _open_ec(work), _open_ec(work)
    try:
        for key in (1, 60, 120):            # first, middle, last entry
            assert ev.read_needle(key).data == payloads[key]
        ev.delete_needle(60)
        ev.delete_needle(999)               # not there: nothing written
        now = open(work + ".ecx", "rb").read()
        assert len(now) == len(before)
        changed = [i for i in range(len(now)) if now[i] != before[i]]
        pos = 59 * t.NEEDLE_MAP_ENTRY_SIZE + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE
        assert changed and set(changed) <= set(range(pos, pos + t.SIZE_SIZE))
        for vol in (ev, other):
            with pytest.raises(NeedleNotFound):
                vol.find_needle_from_ecx(60)
            assert vol.read_needle(59).data == payloads[59]
            assert vol.read_needle(61).data == payloads[61]
    finally:
        ev.close()
        other.close()
    later = _open_ec(work)
    try:
        with pytest.raises(NeedleNotFound):
            later.find_needle_from_ecx(60)
        assert later.read_needle(120).data == payloads[120]
    finally:
        later.close()


def test_an_index_written_anew_leaves_a_mounted_volume_its_own(ec_base,
                                                               tmp_path):
    """`write_sorted_file_from_idx` moves the new `.ecx` over the old
    name: the volume that mapped the old file keeps searching it (a
    file cut to nothing under a mapping would be a SIGBUS)."""
    import shutil
    base, payloads = ec_base
    work = str(tmp_path / "1")
    for ext in [".idx", ".ecx"] + [to_ext(i) for i in range(TOTAL_SHARDS)]:
        shutil.copyfile(base + ext, work + ext)
    ev = _open_ec(work)
    try:
        old = os.stat(work + ".ecx").st_ino
        write_sorted_file_from_idx(work)
        assert os.stat(work + ".ecx").st_ino != old
        assert not os.path.exists(work + ".ecx.tmp")
        assert open(work + ".ecx", "rb").read() == \
            open(base + ".ecx", "rb").read()
        assert ev.read_needle(77).data == payloads[77]
    finally:
        ev.close()


def test_an_empty_index_mounts_and_finds_nothing(ec_base, tmp_path):
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    for i in range(TOTAL_SHARDS):
        shutil.copyfile(base + to_ext(i), work + to_ext(i))
    open(work + ".ecx", "wb").close()
    ev = _open_ec(work, version=3)
    try:
        with pytest.raises(NeedleNotFound):
            ev.find_needle_from_ecx(1)
        ev.delete_needle(1)                 # no entry, no journal line
        assert not os.path.exists(work + ".ecj")
    finally:
        ev.close()


def test_locate_data_boundaries():
    """Port of TestLocateData (ec_test.go:189-200)."""
    intervals = locate_data(LARGE, SMALL, DATA_SHARDS * LARGE + 1,
                            DATA_SHARDS * LARGE, 1)
    assert len(intervals) == 1
    iv = intervals[0]
    assert not iv.is_large_block
    assert iv.block_index == 0 and iv.inner_block_offset == 0 and iv.size == 1

    intervals = locate_data(LARGE, SMALL, DATA_SHARDS * LARGE + 1, 125, 200)
    assert len(intervals) == 1
    sid, off = intervals[0].to_shard_id_and_offset(LARGE, SMALL)
    assert sid == 0 and off == 125

    # Span across a large-block boundary.
    intervals = locate_data(LARGE, SMALL, DATA_SHARDS * LARGE + 1,
                            LARGE - 50, 100)
    assert len(intervals) == 2
    assert intervals[0].size == 50 and intervals[1].size == 50
    assert intervals[1].block_index == 1


def test_too_many_shards_missing_raises(ec_base, tmp_path):
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    shutil.copyfile(base + ".ecx", work + ".ecx")
    for i in range(9):
        shutil.copyfile(base + to_ext(i), work + to_ext(i))
    ev = _open_ec(work)
    try:
        # Needles living wholly on present shards still read (O(1) local);
        # any needle with an interval on missing shard 9 must raise since
        # only 9 survivors remain (< data_shards).
        hit_missing = 0
        for nid in ec_base[1]:
            _, _, intervals = ev.locate_needle(nid)
            on_missing = any(
                iv.to_shard_id_and_offset(LARGE, SMALL)[0] == 9
                for iv in intervals)
            if on_missing:
                hit_missing += 1
                with pytest.raises(ShardsUnavailable):
                    ev.read_needle(nid)
            else:
                ev.read_needle(nid)
        assert hit_missing > 0
    finally:
        ev.close()


def test_shard_bits():
    b = ShardBits(0)
    b = b.add_shard_id(0).add_shard_id(5).add_shard_id(13)
    assert b.shard_ids() == [0, 5, 13]
    assert b.shard_id_count() == 3
    assert b.has_shard_id(5) and not b.has_shard_id(4)
    assert b.remove_shard_id(5).shard_ids() == [0, 13]
    assert b.minus_parity_shards().shard_ids() == [0, 5]
    other = ShardBits(0).add_shard_id(0).add_shard_id(1)
    assert b.plus(other).shard_ids() == [0, 1, 5, 13]
    assert b.minus(other).shard_ids() == [5, 13]


def test_cross_backend_shard_files_identical(ec_base, tmp_path):
    """jax-backend encode produces byte-identical shard files to numpy."""
    import shutil
    base, _ = ec_base
    work = str(tmp_path / "1")
    shutil.copyfile(base + ".dat", work + ".dat")
    shutil.copyfile(base + ".idx", work + ".idx")
    write_ec_files(work, coder=new_coder(backend="jax"),
                   large_block_size=LARGE, small_block_size=SMALL,
                   chunk_size=SMALL)
    for i in range(TOTAL_SHARDS):
        assert open(work + to_ext(i), "rb").read() == \
            open(base + to_ext(i), "rb").read(), f"shard {i}"


# -- golden byte-compatibility gate ------------------------------------------

REF_EC = "/root/reference/weed/storage/erasure_coding"
GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures", "golden_ec")


@pytest.mark.skipif(not os.path.exists(os.path.join(REF_EC, "1.dat")),
                    reason="reference fixture not present")
def test_golden_manifest(tmp_path):
    """Regenerate .ec00-.ec13/.ecx from the reference's committed 1.dat
    at the reference test's block sizes and assert byte-for-byte
    equality with the pinned manifest — freezing the matrix
    construction, GF tables, stripe layout and .ecx sort (see
    fixtures/golden_ec/README.md for validating the same hashes
    against the Go reference)."""
    import hashlib
    import shutil
    shutil.copy(os.path.join(REF_EC, "1.dat"), tmp_path / "1.dat")
    shutil.copy(os.path.join(REF_EC, "1.idx"), tmp_path / "1.idx")
    write_ec_files(str(tmp_path / "1"), large_block_size=LARGE,
                   small_block_size=SMALL)
    write_sorted_file_from_idx(str(tmp_path / "1"))
    want = {}
    with open(os.path.join(GOLDEN, "MANIFEST.sha256")) as f:
        for line in f:
            digest, size, name = line.split()
            want[name] = (digest, int(size))
    assert len(want) == 15
    for name, (digest, size) in want.items():
        blob = (tmp_path / name).read_bytes()
        assert len(blob) == size, f"{name}: size {len(blob)} != {size}"
        got = hashlib.sha256(blob).hexdigest()
        assert got == digest, f"{name}: bytes drifted ({got[:16]}...)"


def test_parity_matrix_pinned_constants():
    """The RS(10,4) systematic matrix (klauspost buildMatrix: extended
    Vandermonde x inverse of its top square) — the full 4x10 parity
    coefficient block is frozen to the values this construction
    produced at pin time, so any drift in the GF tables or the matrix
    algebra fails loudly, independent of the file pipeline."""
    from seaweedfs_tpu.ops.gf256 import build_systematic_matrix
    m = build_systematic_matrix(10, 14)
    assert np.array_equal(m[:10], np.eye(10, dtype=np.uint8))
    assert m[10:].tolist() == [
        [129, 150, 175, 184, 210, 196, 254, 232, 3, 2],
        [150, 129, 184, 175, 196, 210, 232, 254, 2, 3],
        [191, 214, 98, 10, 6, 111, 223, 183, 5, 4],
        [214, 191, 10, 98, 111, 6, 183, 223, 4, 5],
    ]


def test_pipelined_encode_failure_propagates_promptly(tmp_path):
    """A coder failure mid-stream must raise out of write_ec_files —
    not deadlock the read-ahead thread on the full queue (review
    finding, reproduced as a hang before the fix)."""
    import threading
    import time as _t

    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder

    blob = os.urandom(LARGE * DATA_SHARDS * 3)
    with open(tmp_path / "v.dat", "wb") as f:
        f.write(blob)

    class ExplodingCoder(NumpyCoder):
        calls = 0

        def encode(self, data):
            type(self).calls += 1
            if type(self).calls >= 2:
                raise RuntimeError("device fell over")
            return super().encode(data)

    result: list = []

    def run():
        try:
            write_ec_files(str(tmp_path / "v"),
                           coder=ExplodingCoder(10, 4),
                           large_block_size=LARGE, small_block_size=SMALL,
                           chunk_size=LARGE)
            result.append("no-error")
        except RuntimeError as e:
            result.append(str(e))

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(timeout=15)
    assert not th.is_alive(), "write_ec_files deadlocked on coder failure"
    assert result == ["device fell over"]


# -- the seal's read side: in place, into pooled host buffers -------------------

CHUNK = 4 * SMALL   # four small rows per coder call: (10, 400) chunks


@pytest.fixture
def pool(monkeypatch):
    """A pool of this test's own, its buffers one test chunk wide (the
    process's holds 40 MiB buffers and other tests' leftovers)."""
    from seaweedfs_tpu.ec import encoder
    p = encoder._ChunkPool(encoder.CHUNK_POOL_BUFFERS, DATA_SHARDS * CHUNK)
    monkeypatch.setattr(encoder, "CHUNK_POOL", p)
    return p


def _taken(p) -> int:
    c = p.counts()
    return c["reused"] + c["allocated"]


def _write_dat(path, blob: bytes) -> str:
    with open(str(path) + ".dat", "wb") as f:
        f.write(blob)
    return str(path)


def _seal(base: str, coder, chunk_size: int = CHUNK) -> None:
    write_ec_files(base, coder=coder, large_block_size=LARGE,
                   small_block_size=SMALL, chunk_size=chunk_size)


def _reference_shards(blob: bytes, chunk_size: int = CHUNK) -> list[bytes]:
    """The plain reference: the parent commit's serial chunking (a
    fresh zeroed chunk, one copy per block) through NumpyCoder."""
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    coder = NumpyCoder(10, 4)
    shards = [bytearray() for _ in range(TOTAL_SHARDS)]

    def block(off: int, n: int) -> np.ndarray:
        out = np.zeros(n, np.uint8)
        raw = np.frombuffer(blob[off:off + n], np.uint8)
        out[:len(raw)] = raw
        return out

    def emit(data: np.ndarray) -> None:
        for sid, row in enumerate(np.concatenate([data,
                                                  coder.encode(data)])):
            shards[sid] += row.tobytes()

    remaining, processed = len(blob), 0
    chunk = min(chunk_size, LARGE)
    while remaining > LARGE * DATA_SHARDS:
        for b in range(0, LARGE, chunk):
            emit(np.stack([block(processed + i * LARGE + b, chunk)
                           for i in range(DATA_SHARDS)]))
        remaining -= LARGE * DATA_SHARDS
        processed += LARGE * DATA_SHARDS
    row_bytes = SMALL * DATA_SHARDS
    while remaining > 0:
        nrows = min(max(1, chunk_size // SMALL), -(-remaining // row_bytes))
        emit(np.concatenate(
            [np.stack([block(processed + r * row_bytes + i * SMALL, SMALL)
                       for i in range(DATA_SHARDS)])
             for r in range(nrows)], axis=1))
        remaining -= row_bytes * nrows
        processed += row_bytes * nrows
    return [bytes(s) for s in shards]


def _assert_sealed_like_reference(base: str, blob: bytes,
                                  chunk_size: int = CHUNK) -> None:
    from seaweedfs_tpu.ec.integrity import (BlockCrcAccumulator,
                                            ShardChecksums)
    want = _reference_shards(blob, chunk_size)
    ecc = ShardChecksums.load(base)
    for sid in range(TOTAL_SHARDS):
        with open(base + to_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid}"
        acc = BlockCrcAccumulator()
        acc.feed(want[sid])
        assert ecc.get(sid) == acc.finalize(), f".ecc of shard {sid}"


def _coder(backend: str):
    try:
        return new_coder(backend=backend)
    except RuntimeError as e:   # the native library did not build
        pytest.skip(str(e))


@pytest.mark.parametrize("backend", ["numpy", "native", "jax"])
@pytest.mark.parametrize("size", [
    pytest.param(3 * DATA_SHARDS * CHUNK, id="whole_chunks"),
    pytest.param(3 * DATA_SHARDS * CHUNK + 1, id="one_byte_more"),
    pytest.param(3 * DATA_SHARDS * CHUNK + DATA_SHARDS * SMALL + 250,
                 id="partial_last_row"),
    pytest.param(37, id="under_one_row"),
    pytest.param(2 * DATA_SHARDS * LARGE + 4321, id="large_block_rows"),
])
def test_seal_in_dirty_pooled_buffers_is_byte_identical(
        tmp_path, pool, size, backend):
    """Every shard and `.ecc` entry of a seal that runs SECOND, in
    buffers an all-0xFF volume (and a brush, so that no thread timing
    leaves one clean) has dirtied: a tail past the end of the `.dat`
    that was not zeroed shows in data shards, parity and `.ecc`."""
    coder = _coder(backend)
    _seal(_write_dat(tmp_path / "ff", b"\xff" * (8 * DATA_SHARDS * CHUNK)),
          new_coder(backend="numpy"))
    held = [pool.take(1) for _ in range(pool.bound)]
    for buf in held:
        buf[:] = 0xFF
        pool.give(buf)
    assert pool.counts()["held_bytes"] == pool.bound * pool.nbytes
    blob = random.Random(size).randbytes(size)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, coder)
    _assert_sealed_like_reference(base, blob)


def test_chunk_is_the_coders_until_its_parity_is_drained(tmp_path, pool):
    """A coder whose handle reads its input only when drained (as an
    unfenced device coder may): the parity is still the reference's,
    which it is not if a buffer goes back to the reader before
    `flush_one` has drained its chunk."""
    import time as _t

    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder

    class LazyCoder(NumpyCoder):
        def encode(self, data):
            class Handle:
                def __array__(_self, dtype=None, copy=None):
                    return NumpyCoder.encode(self, data)
            _t.sleep(0.002)    # the reader runs ahead meanwhile
            return Handle()

    blob = random.Random(5).randbytes(16 * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, LazyCoder(10, 4))
    _assert_sealed_like_reference(base, blob)


def test_failure_while_the_reader_waits_for_a_buffer(tmp_path, pool):
    """The main thread raises while the read-ahead thread waits for a
    FREE BUFFER (all of the job's are live): the job ends promptly,
    what never reached the coder is back in the pool, and the next job
    seals in it as if nothing had happened."""
    import threading
    import time as _t

    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder

    from seaweedfs_tpu.ec import encoder
    buffers = encoder.SEAL_BUFFERS
    blob = random.Random(6).randbytes((buffers + 4) * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)

    class ExplodingCoder(NumpyCoder):
        calls = 0

        def encode(self, data):
            type(self).calls += 1
            if type(self).calls >= 2:
                deadline = _t.monotonic() + 10
                while (_taken(pool) < buffers
                       and _t.monotonic() < deadline):
                    _t.sleep(0.001)
                _t.sleep(0.05)    # ... and it is waiting for one more
                raise RuntimeError("device fell over")
            return super().encode(data)

    result: list = []

    def run():
        try:
            _seal(base, ExplodingCoder(10, 4))
            result.append("no-error")
        except RuntimeError as e:
            result.append(str(e))

    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(timeout=15)
    assert not th.is_alive(), "write_ec_files deadlocked on coder failure"
    assert result == ["device fell over"]
    # All of the job's were live: two in flight (dropped: a coder may
    # still read them), the others read ahead and handed back.
    assert _taken(pool) == buffers
    assert pool.counts()["held_bytes"] == (buffers - 2) * pool.nbytes
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-")]
    _seal(base, NumpyCoder(10, 4))
    _assert_sealed_like_reference(base, blob)
    assert pool.counts()["held_bytes"] <= pool.bound * pool.nbytes


def test_second_seal_allocates_nothing_and_the_bound_holds(tmp_path, pool):
    """The pool outlives the job: a first seal allocates what it has
    live, a second one in the same process allocates 0, `/debug/device`
    says so, and two jobs at once leave no more than the bound behind."""
    import threading
    import time as _t

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    from seaweedfs_tpu.stats import roofline
    buffers, chunks = encoder.SEAL_BUFFERS, encoder.SEAL_BUFFERS + 4

    class ReaderFirstCoder(NumpyCoder):
        """The first call of each job waits until the read-ahead thread
        has every buffer a job may have live, so the counts below do
        not depend on how the threads were scheduled."""

        def __init__(self, want_taken: int):
            super().__init__(10, 4)
            self.want_taken = want_taken

        def encode(self, data):
            deadline = _t.monotonic() + 10
            while (_taken(pool) < self.want_taken
                   and _t.monotonic() < deadline):
                _t.sleep(0.001)
            return super().encode(data)

    blob = random.Random(7).randbytes(chunks * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, ReaderFirstCoder(buffers))
    first = pool.counts()
    assert first == {"reused": chunks - buffers, "allocated": buffers,
                     "held_bytes": buffers * pool.nbytes}
    _seal(base, ReaderFirstCoder(chunks + buffers))
    second = pool.counts()
    assert second["allocated"] == first["allocated"]
    assert second["reused"] == first["reused"] + chunks
    assert roofline.debug_doc("n:1", "volume")["seal_buffers"] == second
    _assert_sealed_like_reference(base, blob)

    # Two jobs at once, twice a job's buffers live between them.
    bases = [_write_dat(tmp_path / f"w{i}", blob) for i in range(2)]
    coder = ReaderFirstCoder(2 * chunks + 2 * buffers)
    jobs = [threading.Thread(target=_seal, args=(b, coder), daemon=True)
            for b in bases]
    for th in jobs:
        th.start()
    for th in jobs:
        th.join(timeout=30)
        assert not th.is_alive()
    third = pool.counts()
    assert third["allocated"] >= second["allocated"] + buffers
    assert third["held_bytes"] == pool.bound * pool.nbytes
    for b in bases:
        _assert_sealed_like_reference(b, blob)


def test_batch_reader_owns_the_chunks_it_is_given(tmp_path, pool):
    """`_chunk_reader` (the batch path's reader: no buffer passed)
    yields arrays of its own: two chunks taken and held share no
    memory, hold the chunking's bytes, and the pool is not asked."""
    from seaweedfs_tpu.ec.encoder import _chunk_reader
    blob = random.Random(8).randbytes(2 * DATA_SHARDS * CHUNK + 123)
    base = _write_dat(tmp_path / "v", blob)
    with open(base + ".dat", "rb") as dat:
        chunks = list(_chunk_reader(dat, len(blob), LARGE, SMALL, CHUNK))
    assert [c.shape for c in chunks] == [(10, CHUNK), (10, CHUNK),
                                         (10, SMALL)]
    assert all(c.flags.c_contiguous for c in chunks)
    assert not np.shares_memory(chunks[0], chunks[1])
    want = _reference_shards(blob)
    for sid in range(DATA_SHARDS):
        assert b"".join(c[sid].tobytes() for c in chunks) == want[sid]
    assert _taken(pool) == 0


# -- the device round trip of a chunk runs beside the data-shard writes -------

@pytest.fixture
def inflight(monkeypatch):
    """A drain count of this test's own (the process's has every other
    test's seals in it)."""
    from seaweedfs_tpu.ec import encoder
    c = encoder._InflightCount()
    monkeypatch.setattr(encoder, "SEAL_INFLIGHT", c)
    return c


class _HandleCoder:
    """NumpyCoder behind handles that behave as a device array does:
    `encode` returns at once, the handle can be asked to copy back and
    whether it is ready, and is computed when materialised.  Every
    call goes into one event log, with the chunk's number."""

    def __init__(self, log: list, ready: bool = True,
                 fail_at: int | None = None):
        from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
        self._np = NumpyCoder(10, 4)
        self.data_shards, self.parity_shards = 10, 4
        self.total_shards, self.codec = 14, self._np.codec
        self.log, self.ready, self.fail_at = log, ready, fail_at
        self.chunks: list[np.ndarray] = []

    def encode(self, data):
        coder, k = self, len(self.chunks)
        self.chunks.append(data)
        self.log.append(("call", k))

        class Handle:
            def copy_to_host_async(_self):
                coder.log.append(("copy_back", k))

            def is_ready(_self):
                coder.log.append(("is_ready", k))
                return coder.ready

            def __array__(_self, dtype=None, copy=None):
                coder.log.append(("array", k))
                if k == coder.fail_at:
                    raise RuntimeError("device fell over")
                return coder._np.encode(data)
        return Handle()


def test_copy_back_is_requested_at_dispatch_and_collected_at_drain(
        tmp_path, pool, inflight, monkeypatch):
    """One event log, the main thread's part of it whole: per chunk the
    coder is called and the copy back is requested once; the handle is
    asked whether it is ready and materialised only in the drain,
    `SEAL_DEPTH - 1` dispatches later, and the tail is drained oldest
    first.  The writes are the writer threads': a chunk's data rows
    are written after its copy back was requested (they are handed over
    after it), its parity rows after its handle was materialised."""
    from seaweedfs_tpu.ec import encoder
    log: list = []
    real_write = encoder._shard_write

    def logged_write(f, sid, buf, accs):
        log.append(("write", sid))
        real_write(f, sid, buf, accs)

    monkeypatch.setattr(encoder, "_shard_write", logged_write)
    chunks, later = 6, encoder.SEAL_DEPTH - 1
    assert 0 < later < chunks
    blob = random.Random(9).randbytes(chunks * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, _HandleCoder(log))

    want: list = []
    for k in range(chunks):
        want += [("call", k), ("copy_back", k)]
        if k >= later:
            want += [("is_ready", k - later), ("array", k - later)]
    for k in range(chunks - later, chunks):
        want += [("is_ready", k), ("array", k)]
    assert [e for e in log if e[0] != "write"] == want
    for sid in range(TOTAL_SHARDS):
        # a shard's k-th write is chunk k's row
        written = [i for i, e in enumerate(log) if e == ("write", sid)]
        assert len(written) == chunks
        before = "copy_back" if sid < DATA_SHARDS else "array"
        for k, i in enumerate(written):
            assert log.index((before, k)) < i, (sid, k)
    assert inflight.counts() == {"ready": chunks, "waited": 0}
    _assert_sealed_like_reference(base, blob)


@pytest.mark.parametrize("handles,want", [
    pytest.param("ready", {"ready": 5, "waited": 0}, id="ready"),
    pytest.param("not_ready", {"ready": 0, "waited": 5}, id="not_ready"),
    pytest.param("arrays", {"ready": 5, "waited": 0}, id="plain_arrays"),
])
def test_seal_inflight_counts_what_the_drain_found(
        tmp_path, pool, inflight, handles, want):
    """`seal_inflight` of `/debug/device`: a chunk whose handle says it
    is ready counts `ready`, one that does not `waited`, and a host
    coder's plain array (no `is_ready`, nothing to copy back) `ready`."""
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    from seaweedfs_tpu.stats import roofline
    coder = NumpyCoder(10, 4) if handles == "arrays" \
        else _HandleCoder([], ready=handles == "ready")
    blob = random.Random(10).randbytes(5 * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, coder)
    assert inflight.counts() == want
    assert roofline.debug_doc("n:1", "volume")["seal_inflight"] == want
    _assert_sealed_like_reference(base, blob)


def test_a_handle_that_fails_at_the_drain_fails_the_job(
        tmp_path, pool, inflight):
    """An unfenced device coder's error surfaces where the handle is
    collected: the job raises it, every thread is joined, and the
    buffers of the chunks in flight (the failed one and those
    dispatched after it, which the coder may still read) are dropped,
    not handed back to the pool."""
    import threading

    from seaweedfs_tpu.ec import encoder
    depth = encoder.SEAL_DEPTH
    log: list = []
    coder = _HandleCoder(log, fail_at=1)
    blob = random.Random(11).randbytes(
        (encoder.SEAL_BUFFERS + 4) * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    with pytest.raises(RuntimeError, match="device fell over"):
        _seal(base, coder)
    assert not [th for th in threading.enumerate()
                if th.name.startswith("ec-")]
    # chunk 1 failed in the drain that follows chunk `depth`'s dispatch
    assert [e for e in log if e[0] == "call"] == \
        [("call", k) for k in range(depth + 1)]
    assert log[-1] == ("array", 1)
    for data in coder.chunks[1:]:
        assert not any(np.shares_memory(data, buf) for buf in pool._free)
    c = pool.counts()
    assert c["held_bytes"] <= (c["allocated"] - depth) * pool.nbytes
    assert inflight.counts() == {"ready": 2, "waited": 0}
    _seal(base, _HandleCoder([]))
    _assert_sealed_like_reference(base, blob)


# -- the seal's shard writes run beside the main thread -----------------------

@pytest.fixture
def writer_count(monkeypatch):
    """A hand-over count of this test's own (as `inflight`)."""
    from seaweedfs_tpu.ec import encoder
    c = encoder._InflightCount()
    monkeypatch.setattr(encoder, "SEAL_WRITER", c)
    return c


class _KeepingCoder(_HandleCoder):
    """`_HandleCoder` that keeps the parity array each handle gave the
    drain, so that a test can tell whose rows a writer was handed."""

    def __init__(self, log: list, **kw):
        super().__init__(log, **kw)
        self.parities: dict[int, np.ndarray] = {}

    def encode(self, data):
        coder, k, inner = self, len(self.chunks), super().encode(data)

        class Handle:
            copy_to_host_async = inner.copy_to_host_async
            is_ready = inner.is_ready

            def __array__(_self, dtype=None, copy=None):
                coder.parities[k] = inner.__array__()
                return coder.parities[k]
        return Handle()


def _chunk_of(coder, sid: int, buf) -> int | None:
    """The chunk whose row of shard `sid` the object `buf` IS — the
    same bytes at the same address, in the pooled chunk for a data
    shard (the newest chunk read there: buffers are reused), in the
    collected parity for a parity shard; None for a copy."""
    def at(a) -> int:
        return np.asarray(a).__array_interface__["data"][0]

    if sid < DATA_SHARDS:
        whose, row = reversed(list(enumerate(coder.chunks))), sid
    else:
        whose, row = coder.parities.items(), sid - DATA_SHARDS
    return next((k for k, a in whose
                 if at(buf) == at(a[row]) and len(buf) == a.shape[1]),
                None)


def _record_writes(monkeypatch, log: list, coder, gate=None):
    """`_shard_write` logs `("write", sid, chunk, thread)` before it
    writes, `chunk` by `_chunk_of`; with a `gate` (an Event) no row is
    written before it is set."""
    import threading

    from seaweedfs_tpu.ec import encoder
    real_write = encoder._shard_write

    def logged_write(f, sid, buf, accs):
        if gate is not None:
            assert gate.wait(10)
        log.append(("write", sid, _chunk_of(coder, sid, buf),
                    threading.current_thread().name))
        real_write(f, sid, buf, accs)

    monkeypatch.setattr(encoder, "_shard_write", logged_write)


def _record_gives(monkeypatch, log: list, pool, coder) -> None:
    """`pool.give` logs `("give", chunk)`: the first chunk read into
    that buffer which has not been handed back yet."""
    real_give, given = pool.give, set()

    def logged_give(buf):
        k = next(k for k, data in enumerate(coder.chunks)
                 if k not in given and np.shares_memory(buf, data))
        given.add(k)
        log.append(("give", k))
        real_give(buf)

    monkeypatch.setattr(pool, "give", logged_give)


def test_rows_reach_the_writers_as_views_one_thread_a_shard(
        tmp_path, pool, monkeypatch):
    """No `tobytes` on the seal's write path: every object
    `_shard_write` receives shares memory with the pooled chunk (a data
    row) or with the collected parity array (a parity row).  And a
    shard file is one writer thread's, in chunk order: `SEAL_WRITERS`
    threads, none of them the job's, and none left afterwards."""
    import threading

    from seaweedfs_tpu.ec import encoder
    log: list = []
    coder = _KeepingCoder([])
    _record_writes(monkeypatch, log, coder)
    chunks = encoder.SEAL_BUFFERS + 3          # buffers are reused
    blob = random.Random(13).randbytes(chunks * DATA_SHARDS * CHUNK - 777)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, coder)
    monkeypatch.undo()
    threads = set()
    for sid in range(TOTAL_SHARDS):
        mine = [e for e in log if e[1] == sid]
        assert [e[2] for e in mine] == list(range(chunks)), sid
        assert len({e[3] for e in mine}) == 1, sid
        threads.add(mine[0][3])
    assert len(threads) == encoder.SEAL_WRITERS
    assert all(name.startswith("ec-write-") for name in threads)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-")]
    _assert_sealed_like_reference(base, blob)


@pytest.mark.parametrize("last", ["the_drain", "the_writes"])
def test_buffer_goes_back_when_rows_are_written_and_parity_drained(
        tmp_path, pool, monkeypatch, last):
    """A chunk's buffer is the coder's until its parity is drained AND
    the writers' until its data rows are in their files: whichever
    comes last hands it back, and nothing before."""
    import threading
    import time as _t

    from seaweedfs_tpu.ec import encoder
    depth = encoder.SEAL_DEPTH
    log: list = []
    gate = threading.Event()
    if last == "the_drain":
        gate.set()

    def data_rows_of_chunk_0() -> int:
        return len([e for e in log
                    if e[0] == "write" and e[1] < DATA_SHARDS
                    and e[2] == 0])

    class Coder(_KeepingCoder):
        def encode(self, data):
            k = len(self.chunks)
            if last == "the_drain" and k == depth - 1:
                # chunk 0 is drained after this dispatch: its rows
                # are in their files before that
                deadline = _t.monotonic() + 10
                while (data_rows_of_chunk_0() < DATA_SHARDS
                       and _t.monotonic() < deadline):
                    _t.sleep(0.001)
                _t.sleep(0.02)      # ... and the last row's `done` ran
                log.append(("rows_written", 0))
            if last == "the_writes" and k == depth:
                # chunk 0 was drained before this dispatch, and no row
                # of any chunk is written yet
                assert ("array", 0) in log
                log.append(("drained", 0))
                gate.set()
            return super().encode(data)

    coder = Coder(log)
    _record_writes(monkeypatch, log, coder, gate)
    _record_gives(monkeypatch, log, pool, coder)
    blob = random.Random(14).randbytes((depth + 2) * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, coder)
    monkeypatch.undo()
    given = log.index(("give", 0))
    rows = [i for i, e in enumerate(log)
            if e[0] == "write" and e[1] < DATA_SHARDS and e[2] == 0]
    assert len(rows) == DATA_SHARDS
    assert given > max(rows) and given > log.index(("array", 0))
    if last == "the_drain":
        assert max(rows) < log.index(("rows_written", 0)) \
            < log.index(("array", 0)) < given
    else:
        assert log.index(("array", 0)) < log.index(("drained", 0)) \
            < min(rows)
    # every chunk's buffer came back, once
    assert sorted(e[1] for e in log if e[0] == "give") == \
        list(range(depth + 2))
    _assert_sealed_like_reference(base, blob)


@pytest.mark.parametrize("rows", ["data", "parity"])
def test_a_full_disk_on_a_writer_ends_the_seal_promptly(
        tmp_path, pool, monkeypatch, rows):
    """`OSError(28)` out of a shard write on a writer thread — of a
    data row, of a parity row — is raised out of `write_ec_files`
    within seconds; no pipeline thread is left; every buffer no coder
    may still read is back in the pool (the chunks that were dispatched
    and not drained keep theirs) and the pool stays at its bound; the
    next seal writes the reference's bytes."""
    import threading
    import time as _t

    from seaweedfs_tpu.ec import encoder
    real_write = encoder._shard_write
    sid_at_fault = 7 if rows == "data" else 12
    seen: list = []

    def full_disk(f, sid, buf, accs):
        if sid == sid_at_fault:
            seen.append(sid)
            if len(seen) == 3:               # its third chunk
                raise OSError(28, "No space left on device")
        real_write(f, sid, buf, accs)

    monkeypatch.setattr(encoder, "_shard_write", full_disk)
    log: list = []
    coder = _HandleCoder(log)
    blob = random.Random(15).randbytes(24 * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    result: list = []

    def run():
        try:
            _seal(base, coder)
            result.append("no-error")
        except OSError as e:
            result.append(e)

    th = threading.Thread(target=run, daemon=True)
    t0 = _t.monotonic()
    th.start()
    th.join(timeout=15)
    assert not th.is_alive(), "write_ec_files hung on the full disk"
    assert _t.monotonic() - t0 < 5
    assert isinstance(result[0], OSError) and result[0].errno == 28
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-")]
    assert len(coder.chunks) < 24            # it did not run to the end
    undrained = [k for k in range(len(coder.chunks))
                 if ("array", k) not in log]
    for k in undrained:
        assert not any(np.shares_memory(coder.chunks[k], buf)
                       for buf in pool._free)
    c = pool.counts()
    assert c["allocated"] <= encoder.SEAL_BUFFERS <= pool.bound
    assert c["held_bytes"] == \
        (c["allocated"] - len(undrained)) * pool.nbytes
    monkeypatch.undo()
    _seal(base, _HandleCoder([]))
    _assert_sealed_like_reference(base, blob)
    assert pool.counts()["held_bytes"] <= pool.bound * pool.nbytes


def test_the_reads_of_a_chunk_go_out_side_by_side(
        tmp_path, pool, monkeypatch):
    """The seal's reader spreads a chunk's `preadv` calls (four a chunk
    of four small-block rows, as on the chip) over its pool: all four
    are inside `preadv` at once (a barrier only all of them together
    pass), none on the job's thread or the read-ahead thread, and no
    thread is left when the job ends."""
    import threading

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    reads = CHUNK // SMALL
    assert 1 < reads <= encoder.SEAL_READERS
    barrier = threading.Barrier(reads, timeout=10)
    real_preadv, names = os.preadv, set()

    def together(fd, views, offset):
        names.add(threading.current_thread().name)
        barrier.wait()
        return real_preadv(fd, views, offset)

    monkeypatch.setattr(encoder.os, "preadv", together)
    blob = random.Random(16).randbytes(3 * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    _seal(base, NumpyCoder(10, 4))
    monkeypatch.undo()
    assert len(names) >= reads
    assert all(n.startswith("ec-seal-read") for n in names)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-")]
    _assert_sealed_like_reference(base, blob)


def test_seal_writer_and_the_writers_row_count_what_happened(
        tmp_path, pool, writer_count, monkeypatch):
    """`seal_writer` of `/debug/device` notes every chunk's hand-over:
    `ready` while the writers are inside the window, `waited` once they
    are `SEAL_DEPTH` chunks behind (here: held at a gate until the main
    thread waits for them).  `beside.seal_write` has a count a row and
    the rows' bytes; the main thread's `seal.write_data` and
    `seal.write_parity` keep a count a chunk (and one: the last wait)
    and the bytes handed over."""
    import threading
    import time as _t

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.stats import roofline
    from seaweedfs_tpu.stats.roofline import StageClock
    depth, chunks = encoder.SEAL_DEPTH, encoder.SEAL_DEPTH + 3
    gate = threading.Event()
    coder = _KeepingCoder([])
    _record_writes(monkeypatch, [], coder, gate)

    def open_when_the_main_thread_waits():
        deadline = _t.monotonic() + 10
        while (not writer_count.counts()["waited"]
               and _t.monotonic() < deadline):
            _t.sleep(0.001)
        gate.set()

    opener = threading.Thread(target=open_when_the_main_thread_waits,
                              daemon=True)
    opener.start()
    blob = random.Random(17).randbytes(chunks * DATA_SHARDS * CHUNK)
    base = _write_dat(tmp_path / "v", blob)
    clock = StageClock("rs")
    write_ec_files(base, coder=coder, large_block_size=LARGE,
                   small_block_size=SMALL, chunk_size=CHUNK, clock=clock)
    opener.join(timeout=10)
    assert not opener.is_alive()
    got = writer_count.counts()
    assert got["ready"] + got["waited"] == chunks
    assert got["ready"] >= depth and got["waited"] >= 1
    doc = roofline.debug_doc("n:1", "volume")
    assert doc["seal_writer"] == got
    rows = clock.totals()
    assert rows["beside.seal_write"]["count"] == TOTAL_SHARDS * chunks
    assert rows["beside.seal_write"]["bytes"] == TOTAL_SHARDS * chunks * CHUNK
    assert rows["seal.write_data"]["count"] == chunks
    assert rows["seal.write_data"]["bytes"] == DATA_SHARDS * chunks * CHUNK
    assert rows["seal.write_parity"]["count"] == chunks + 1
    assert rows["seal.write_parity"]["bytes"] == 4 * chunks * CHUNK
    served = {r["kernel"]: r for r in doc["kernels"]}
    assert served["beside.seal_write"]["count"] >= TOTAL_SHARDS * chunks
    _assert_sealed_like_reference(base, blob)


class _UnfencedCoder(_HandleCoder):
    """`_HandleCoder` with the device coder's unfenced entry point."""

    def encode_unfenced(self, data, crc: bool = False):
        assert not crc
        return (self.encode(data),)


@pytest.mark.parametrize("kind", ["host", "unfenced", "pallas_fused",
                                  "pallas_accs"])
def test_every_coder_runs_the_same_loop_byte_identically(
        tmp_path, monkeypatch, kind):
    """A host coder (no `encode_unfenced`: it computes inside
    dispatch), a coder that is called unfenced, the device coder with
    the fused CRC and the same with the byte accumulators (each
    shard's fed by its one writer thread, in file order): one loop,
    and every shard and `.ecc` entry is the reference's."""
    from seaweedfs_tpu.ec import SMALL_BLOCK_SIZE
    from seaweedfs_tpu.ec.integrity import (BlockCrcAccumulator,
                                            ShardChecksums)
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    if kind in ("host", "unfenced"):
        from seaweedfs_tpu.ec import encoder
        monkeypatch.setattr(
            encoder, "CHUNK_POOL",
            encoder._ChunkPool(encoder.CHUNK_POOL_BUFFERS,
                               DATA_SHARDS * CHUNK))
        blob = random.Random(18).randbytes(7 * DATA_SHARDS * CHUNK + 99)
        base = _write_dat(tmp_path / "v", blob)
        _seal(base, NumpyCoder(10, 4) if kind == "host"
              else _UnfencedCoder([]))
        _assert_sealed_like_reference(base, blob)
        return
    from seaweedfs_tpu.ops.coder_pallas import PallasCoder
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC",
                       "1" if kind == "pallas_fused" else "0")
    block = SMALL_BLOCK_SIZE
    blob = random.Random(19).randbytes(4 * DATA_SHARDS * block - 4321)
    base, want = (_write_dat(tmp_path / n, blob) for n in ("v", "w"))
    write_ec_files(base, coder=PallasCoder(), chunk_size=block)
    write_ec_files(want, coder=NumpyCoder(), chunk_size=block)
    ecc, ecc_want = ShardChecksums.load(base), ShardChecksums.load(want)
    for sid in range(TOTAL_SHARDS):
        with open(base + to_ext(sid), "rb") as a, \
                open(want + to_ext(sid), "rb") as b:
            raw = a.read()
            assert raw == b.read(), sid
        acc = BlockCrcAccumulator()
        acc.feed(raw)
        assert ecc.get(sid) == ecc_want.get(sid) == acc.finalize(), sid


def test_seals_at_once_under_a_short_switch_interval(tmp_path, pool):
    """More jobs than a job has writers, the interpreter switching
    threads every few microseconds: every countdown is met exactly
    once — each job's files are the reference's, every buffer is back
    (the pool is at its bound) and no thread is left."""
    import sys
    import threading

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    blob = random.Random(20).randbytes(12 * DATA_SHARDS * CHUNK + 5)
    bases = [_write_dat(tmp_path / f"v{i}", blob)
             for i in range(encoder.SEAL_WRITERS + 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        jobs = [threading.Thread(target=_seal, daemon=True,
                                 args=(b, NumpyCoder(10, 4)))
                for b in bases]
        for th in jobs:
            th.start()
        for th in jobs:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ec-")]
    assert pool.counts()["held_bytes"] == pool.bound * pool.nbytes
    for b in bases:
        _assert_sealed_like_reference(b, blob)


# -- the rebuild runs on the same pipeline, a deeper window in flight ----------

def _sealed(tmp_path, codec: str = "rs", chunks: float = 3.5):
    """Shards of a random volume, `chunks` rebuild chunks of CHUNK bytes
    each (a half: the last one narrower), sealed by the reference
    coder: `(base, {sid: its bytes})`."""
    from seaweedfs_tpu.ec.integrity import ShardChecksums
    size = int(chunks * DATA_SHARDS * CHUNK)
    blob = random.Random(size).randbytes(size - 17)
    base = _write_dat(tmp_path / codec, blob)
    _seal(base, new_coder(backend="numpy", codec=codec))
    shards = {}
    for sid in range(TOTAL_SHARDS):
        with open(base + to_ext(sid), "rb") as f:
            shards[sid] = f.read()
    assert len(shards[0]) == int(chunks * CHUNK)
    assert sorted(ShardChecksums.load(base).shards) == sorted(shards)
    return base, shards


def _lose(base: str, lost) -> None:
    """As `/admin/ec/delete_shards` leaves a volume: the files and
    their `.ecc` entries gone."""
    from seaweedfs_tpu.ec.integrity import ShardChecksums
    ecc = ShardChecksums.load(base)
    for sid in lost:
        os.remove(base + to_ext(sid))
        ecc.drop_shard(sid)
    ecc.save()


def _assert_rebuilt_like_reference(base: str, shards: dict, lost,
                                   codec: str = "rs") -> None:
    """The plain reference: NumpyCoder over the whole surviving shards
    at once, no chunks, no pipeline — and what it gives is what was
    sealed.  Every `.ecc` entry, the survivors' too, is the crc32c of
    those bytes."""
    from seaweedfs_tpu.ec.integrity import (BlockCrcAccumulator,
                                            ShardChecksums)
    want = new_coder(backend="numpy", codec=codec).reconstruct(
        {sid: np.frombuffer(raw, np.uint8)
         for sid, raw in shards.items() if sid not in lost},
        wanted=list(lost))
    ecc = ShardChecksums.load(base)
    for sid, raw in shards.items():
        if sid in lost:
            assert want[sid].tobytes() == raw
        with open(base + to_ext(sid), "rb") as f:
            assert f.read() == raw, f"shard {sid}"
        acc = BlockCrcAccumulator()
        acc.feed(raw)
        assert ecc.get(sid) == acc.finalize(), f".ecc of shard {sid}"


def _dirty(pool) -> None:
    """Every buffer the pool may hold, allocated and full of 0xFF."""
    held = [pool.take(1) for _ in range(pool.bound)]
    for buf in held:
        buf[:] = 0xFF
        pool.give(buf)


@pytest.fixture
def rebuild_inflight(monkeypatch):
    from seaweedfs_tpu.ec import encoder
    c = encoder._InflightCount()
    monkeypatch.setattr(encoder, "REBUILD_INFLIGHT", c)
    return c


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
@pytest.mark.parametrize("codec,lost", [
    pytest.param("rs", [3], id="one_lost"),
    pytest.param("rs", [3, 11], id="two_lost"),
    pytest.param("rs", [0, 5, 11], id="three_lost"),
    pytest.param("rs", [0, 5, 11, 13], id="four_lost"),
    pytest.param("lrc", [8], id="lrc_in_group"),
])
def test_rebuild_in_dirty_pooled_buffers_is_byte_identical(
        tmp_path, pool, backend, codec, lost):
    """Rebuilt shards and `.ecc` against the reference for one to four
    lost shards, a shard size that is no multiple of the chunk (the
    last chunk is half as wide) and an LRC in-group loss (5 survivors
    read: half a buffer), in buffers full of another job's bytes."""
    from seaweedfs_tpu.stats.metrics import ec_repair_read_bytes_total
    base, shards = _sealed(tmp_path, codec)
    _lose(base, lost)
    _dirty(pool)
    before = ec_repair_read_bytes_total.value(codec=codec)
    got = rebuild_ec_files(base, coder=new_coder(backend=backend,
                                                 codec=codec),
                           chunk_size=CHUNK)
    assert got == lost
    reads = 5 if codec == "lrc" else DATA_SHARDS
    assert ec_repair_read_bytes_total.value(codec=codec) - before == \
        reads * len(shards[0])
    _assert_rebuilt_like_reference(base, shards, lost, codec)


def test_unfenced_reconstruct_is_one_call_and_no_kernel_row():
    """`PallasCoder.reconstruct_unfenced` on ONE stacked host array:
    the rows `reconstruct` gives — for exactly the survivors the decode
    reads, for more of them (it takes its own) and for an LRC group's
    five — as one array, and no fenced row in the ledger."""
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    from seaweedfs_tpu.ops.coder_pallas import PallasCoder
    from seaweedfs_tpu.stats import roofline
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, (DATA_SHARDS, 5000), dtype=np.uint8)
    for codec, present, wanted in [
            ("rs", [0, 1, 2, 4, 5, 6, 7, 8, 9, 10], [3, 11]),
            ("rs", [0, 1, 2, 4, 5, 6, 7, 8, 9, 10, 12, 13], [11, 3]),
            ("lrc", [5, 6, 7, 9, 11], [8])]:
        shards = np.asarray(NumpyCoder(codec=codec).encode_all(data))
        coder = PallasCoder(codec=codec)
        counted = sum(r["count"] for r in roofline.LEDGER.kernel_table())
        got = coder.reconstruct_unfenced(
            present, np.ascontiguousarray(shards[present]), wanted)
        assert got.shape == (len(wanted), 5000)
        assert np.array_equal(np.asarray(got), shards[wanted])
        assert sum(r["count"] for r in
                   roofline.LEDGER.kernel_table()) == counted
        assert roofline.LEDGER.has_rows()
    with pytest.raises(ValueError, match="out of range"):
        coder.reconstruct_unfenced([5, 6, 7, 9, 11], shards[:5], [14])


class _RebuildHandleCoder:
    """`_HandleCoder` for the rebuild: `reconstruct_unfenced` returns at
    once a handle that behaves as a device array does and reads the
    stacked chunk only when it is materialised."""

    def __init__(self, log: list, ready: bool = True,
                 fail_at: int | None = None, before_call=None):
        from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
        self._np = NumpyCoder(10, 4)
        self.codec = self._np.codec
        self.log, self.ready, self.fail_at = log, ready, fail_at
        self.before_call = before_call
        self.chunks: list[np.ndarray] = []

    def reconstruct_unfenced(self, present, stacked, wanted):
        coder, k = self, len(self.chunks)
        if self.before_call is not None:
            self.before_call(k)
        self.chunks.append(stacked)
        self.log.append(("call", k))

        class Handle:
            def copy_to_host_async(_self):
                coder.log.append(("copy_back", k))

            def is_ready(_self):
                coder.log.append(("is_ready", k))
                return coder.ready

            def __array__(_self, dtype=None, copy=None):
                coder.log.append(("array", k))
                if k == coder.fail_at:
                    raise RuntimeError("device fell over")
                rec = coder._np.reconstruct(dict(zip(present, stacked)),
                                            wanted=list(wanted))
                return np.stack([rec[sid] for sid in wanted])
        return Handle()


def test_rebuilt_rows_are_collected_the_window_later(
        tmp_path, pool, rebuild_inflight, monkeypatch):
    """One event log of the main thread, whole: per chunk ONE coder
    call on the stacked survivors and the copy back requested at once;
    the handle is asked whether it is ready and materialised only
    `REBUILD_DEPTH - 1` dispatches later, its rows written after that,
    and the tail is drained oldest first."""
    from seaweedfs_tpu.ec import encoder
    chunks, lost = 9, [3, 11]
    base, shards = _sealed(tmp_path, chunks=chunks)
    _lose(base, lost)
    log: list = []
    real_write = encoder._shard_write

    def logged_write(f, sid, buf, accs):
        log.append(("write", sid))
        real_write(f, sid, buf, accs)

    monkeypatch.setattr(encoder, "_shard_write", logged_write)
    coder = _RebuildHandleCoder(log)
    assert rebuild_ec_files(base, coder=coder, chunk_size=CHUNK) == lost
    later = encoder.REBUILD_DEPTH - 1
    assert 1 < later < chunks

    def drained(k):
        return [("is_ready", k), ("array", k)] + \
            [("write", sid) for sid in lost]

    want: list = []
    for k in range(chunks):
        want += [("call", k), ("copy_back", k)]
        if k >= later:
            want += drained(k - later)
    for k in range(chunks - later, chunks):
        want += drained(k)
    assert log == want
    assert [c.shape for c in coder.chunks] == [(DATA_SHARDS, CHUNK)] * chunks
    assert rebuild_inflight.counts() == {"ready": chunks, "waited": 0}
    _assert_rebuilt_like_reference(base, shards, lost)


@pytest.mark.parametrize("handles,want", [
    pytest.param("ready", {"ready": 5, "waited": 0}, id="ready"),
    pytest.param("not_ready", {"ready": 0, "waited": 5}, id="not_ready"),
    pytest.param("arrays", {"ready": 5, "waited": 0}, id="plain_arrays"),
])
def test_rebuild_inflight_counts_what_the_drain_found(
        tmp_path, pool, rebuild_inflight, inflight, handles, want):
    """`rebuild_inflight` of `/debug/device`, as `seal_inflight`: the
    rebuild's own count (the seal's stays 0), a host coder's rows
    `ready`."""
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    from seaweedfs_tpu.stats import roofline
    coder = NumpyCoder(10, 4) if handles == "arrays" \
        else _RebuildHandleCoder([], ready=handles == "ready")
    base, shards = _sealed(tmp_path, chunks=5)
    _lose(base, [3, 11])
    seals = inflight.counts()
    rebuild_ec_files(base, coder=coder, chunk_size=CHUNK)
    assert rebuild_inflight.counts() == want
    doc = roofline.debug_doc("n:1", "volume")
    assert doc["rebuild_inflight"] == want
    assert doc["seal_inflight"] == seals
    _assert_rebuilt_like_reference(base, shards, [3, 11])


def test_survivors_are_the_coders_until_the_rows_are_drained(
        tmp_path, pool):
    """A handle that reads its stacked survivors only when drained (as
    an unfenced device coder may), the reader running ahead meanwhile:
    the rows are still the reference's, which they are not if a buffer
    goes back to the reader before its chunk's rows are collected."""
    import time as _t
    base, shards = _sealed(tmp_path, chunks=16)
    _lose(base, [3, 11])
    coder = _RebuildHandleCoder([], before_call=lambda k: _t.sleep(0.002))
    rebuild_ec_files(base, coder=coder, chunk_size=CHUNK)
    _assert_rebuilt_like_reference(base, shards, [3, 11])


def _all_buffers_read_ahead(pool, taken: int) -> None:
    """Wait until the pool has handed out `taken` buffers: the reader
    thread has every buffer its job may have live."""
    import time as _t
    deadline = _t.monotonic() + 10
    while _taken(pool) < taken and _t.monotonic() < deadline:
        _t.sleep(0.001)
    assert _taken(pool) >= taken


@pytest.mark.parametrize("fault", ["short_read", "drain", "write"])
def test_a_failing_rebuild_ends_promptly_and_hands_its_buffers_back(
        tmp_path, pool, rebuild_inflight, monkeypatch, fault):
    """A short read on the reader thread, a handle that fails where it
    is collected, a write that fails (a full disk): each raises out of
    `rebuild_ec_files` promptly, the reader thread is joined, the
    buffers that never reached the coder are back in the pool and those
    of the chunks in flight (which a coder may still read) are not; the
    next rebuild runs as if nothing had happened."""
    import threading
    import time as _t

    from seaweedfs_tpu.ec import encoder
    depth, buffers = encoder.REBUILD_DEPTH, encoder.REBUILD_BUFFERS
    base, shards = _sealed(tmp_path, chunks=12)
    lost = [3, 11]
    _lose(base, lost)
    # chunk 0's buffer is handed back before its rows are written, so
    # by then the reader can have taken one more than it may hold
    coder = _RebuildHandleCoder(
        [], fail_at=1 if fault == "drain" else None,
        before_call=lambda k: k == depth - 1 and
        _all_buffers_read_ahead(pool, buffers))
    if fault == "short_read":
        real_preadv = os.preadv

        def short(fd, views, offset):
            # the third chunk of every survivor, whichever of the five
            # reader threads makes the call and in whatever order
            n = real_preadv(fd, views, offset)
            return n - 1 if offset == 2 * CHUNK else n
        monkeypatch.setattr(encoder.os, "preadv", short)
        error, dropped = ValueError, 1        # the one being filled
    elif fault == "drain":
        error, dropped = RuntimeError, depth   # chunks 1 .. depth
    else:
        real_write = encoder._shard_write

        def full_disk(f, sid, buf, accs):
            _all_buffers_read_ahead(pool, buffers + 1)
            raise OSError(28, "No space left on device")
        monkeypatch.setattr(encoder, "_shard_write", full_disk)
        error, dropped = OSError, depth - 1    # chunks 1 .. depth - 1
    result: list = []

    def run():
        try:
            rebuild_ec_files(base, coder=coder, chunk_size=CHUNK)
            result.append("no-error")
        except error as e:
            result.append(e)

    th = threading.Thread(target=run, daemon=True)
    t0 = _t.monotonic()
    th.start()
    th.join(timeout=15)
    assert not th.is_alive(), "rebuild_ec_files hung on the failure"
    assert _t.monotonic() - t0 < 5
    assert isinstance(result[0], error), result
    assert not [t for t in threading.enumerate()
                if t.name.startswith(("ec-read-ahead", "ec-rebuild-read"))]
    c = pool.counts()
    assert c["allocated"] <= buffers
    assert c["held_bytes"] == (c["allocated"] - dropped) * pool.nbytes
    for data in coder.chunks[-dropped:] if fault != "short_read" else []:
        assert not any(np.shares_memory(data, buf) for buf in pool._free)
    monkeypatch.undo()
    for sid in lost:
        os.remove(base + to_ext(sid))
    assert rebuild_ec_files(base, coder=_RebuildHandleCoder([]),
                            chunk_size=CHUNK) == lost
    _assert_rebuilt_like_reference(base, shards, lost)


def test_the_survivors_of_a_chunk_are_read_side_by_side(
        tmp_path, pool, monkeypatch):
    """The reader spreads a chunk's reads over `REBUILD_READERS`
    threads: that many are inside `preadv` at once (a barrier only all
    of them together pass), none of them the job's thread or the
    read-ahead thread, and none is left when the job ends."""
    import threading

    from seaweedfs_tpu.ec import encoder
    readers = encoder.REBUILD_READERS
    assert 1 < readers and DATA_SHARDS % readers == 0
    base, shards = _sealed(tmp_path)
    _lose(base, [3, 11])
    barrier = threading.Barrier(readers, timeout=10)
    real_preadv, names = os.preadv, set()

    def together(fd, views, offset):
        names.add(threading.current_thread().name)
        barrier.wait()
        return real_preadv(fd, views, offset)

    monkeypatch.setattr(encoder.os, "preadv", together)
    rebuild_ec_files(base, coder=new_coder(backend="numpy"),
                     chunk_size=CHUNK)
    monkeypatch.undo()
    assert len(names) == readers
    assert all(n.startswith("ec-rebuild-read") for n in names)
    assert not [t for t in threading.enumerate()
                if t.name.startswith(("ec-read-ahead", "ec-rebuild-read"))]
    _assert_rebuilt_like_reference(base, shards, [3, 11])


def test_second_rebuild_allocates_nothing(tmp_path, pool, monkeypatch):
    """The pool is the process's: a first rebuild allocates what it has
    live (`REBUILD_BUFFERS`), a second allocates 0 — nothing per chunk,
    nothing per volume — and `/debug/device` says so."""
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.stats import roofline
    chunks, lost = 9, [3, 11]
    buffers = encoder.REBUILD_BUFFERS
    assert chunks > buffers <= pool.bound
    base, shards = _sealed(tmp_path, chunks=chunks)
    # the seal that made the shards has used `pool`: an empty one
    pool = encoder._ChunkPool(pool.bound, pool.nbytes)
    monkeypatch.setattr(encoder, "CHUNK_POOL", pool)

    def rebuild(taken_before: int):
        _lose(base, lost)
        coder = _RebuildHandleCoder(
            [], before_call=lambda k: k == 0 and
            _all_buffers_read_ahead(pool, taken_before + buffers))
        rebuild_ec_files(base, coder=coder, chunk_size=CHUNK)
        _assert_rebuilt_like_reference(base, shards, lost)
        return pool.counts()

    first = rebuild(0)
    assert first == {"reused": chunks - buffers, "allocated": buffers,
                     "held_bytes": buffers * pool.nbytes}
    second = rebuild(chunks)
    assert second == {"reused": first["reused"] + chunks,
                      "allocated": buffers,
                      "held_bytes": buffers * pool.nbytes}
    assert roofline.debug_doc("n:1", "volume")["seal_buffers"] == second


def test_a_seal_and_a_rebuild_at_once_keep_the_pool_at_its_bound(
        tmp_path, pool, monkeypatch):
    """Both jobs at once, every buffer either may hold live at the same
    time: more than the bound exist for a while, no more than the bound
    stay, and both jobs' files are the reference's."""
    import threading

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    live = encoder.SEAL_BUFFERS + encoder.REBUILD_BUFFERS
    assert pool.bound == max(encoder.SEAL_BUFFERS,
                             encoder.REBUILD_BUFFERS) < live
    lost = [3, 11]
    rebuilt, shards = _sealed(tmp_path, chunks=9)
    _lose(rebuilt, lost)
    # the seal that made the shards has used `pool`: an empty one
    pool = encoder._ChunkPool(pool.bound, pool.nbytes)
    monkeypatch.setattr(encoder, "CHUNK_POOL", pool)
    blob = random.Random(12).randbytes(9 * DATA_SHARDS * CHUNK)
    sealed = _write_dat(tmp_path / "v", blob)

    class BothReadersFirst(NumpyCoder):
        def encode(self, data):
            _all_buffers_read_ahead(pool, live)
            return super().encode(data)

    jobs = [
        threading.Thread(target=_seal, daemon=True,
                         args=(sealed, BothReadersFirst(10, 4))),
        threading.Thread(target=rebuild_ec_files, daemon=True, args=(
            rebuilt, _RebuildHandleCoder(
                [], before_call=lambda k: _all_buffers_read_ahead(
                    pool, live)), CHUNK))]
    for th in jobs:
        th.start()
    for th in jobs:
        th.join(timeout=30)
        assert not th.is_alive()
    c = pool.counts()
    assert c["allocated"] >= live
    assert c["held_bytes"] == pool.bound * pool.nbytes
    _assert_sealed_like_reference(sealed, blob)
    _assert_rebuilt_like_reference(rebuilt, shards, lost)
