"""Streamed EC pipeline (ISSUE 8 / ROADMAP 1): byte-identity of the
overlapped encode path, bit-exactness of the kernel-fused `.ecc`
CRC32-C sidecar, the overlap regression (injected clock, no sleeps),
and the zero-collectives property of the shard_map batch step.

Marker: ecpipe (tier-1).
"""

from __future__ import annotations

import itertools
import json
import os
import re
import threading

import numpy as np
import pytest

from seaweedfs_tpu.core.crc import crc32c
from seaweedfs_tpu.ec import SMALL_BLOCK_SIZE, to_ext
from seaweedfs_tpu.ec.encoder import (write_ec_files,
                                      write_sorted_file_from_idx)
from seaweedfs_tpu.ec.integrity import ShardChecksums, file_block_crcs
from seaweedfs_tpu.ops import crc_fold
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.ops.coder_pallas import PallasCoder
from seaweedfs_tpu.parallel.stream_pipeline import (PipelineRecorder,
                                                    run_pipeline)

pytestmark = pytest.mark.ecpipe

BLOCK = SMALL_BLOCK_SIZE


@pytest.fixture(autouse=True)
def _force_fused(monkeypatch):
    """The fused-CRC default is platform-gated (ON only on TPU, see
    crc_fold.fused_crc_enabled) — force it on so this suite exercises
    the fused paths on the CPU test mesh too."""
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", "1")


# ---------------------------------------------------------------------------
# crc_fold algebra and the fused kernel
# ---------------------------------------------------------------------------

def test_crc_fold_matches_reference_blocks():
    rng = np.random.default_rng(0)
    tile, block = 512, 4096
    rows = rng.integers(0, 256, (3, 3 * block), dtype=np.uint8)
    parts = crc_fold.tile_partials_np(rows, tile, block)
    for r in range(rows.shape[0]):
        got = crc_fold.block_crcs_from_partials(
            parts[r], rows.shape[1], tile, block)
        want = [crc32c(rows[r, b * block:(b + 1) * block].tobytes())
                for b in range(3)]
        assert got == want
    dev = np.asarray(crc_fold.block_crcs_jnp(rows, tile, block))
    assert dev.dtype == np.uint32
    assert [list(map(int, dev[r])) for r in range(3)] == \
        [[crc32c(rows[r, b * block:(b + 1) * block].tobytes())
          for b in range(3)] for r in range(3)]


@pytest.mark.parametrize("codec", ["rs", "lrc"])
@pytest.mark.parametrize("mm", ["bf16", "int8"])
def test_fused_kernel_crcs_bit_exact(codec, mm):
    """The Pallas kernel's second output is the exact crc32c of every
    `.ecc` block of every shard row — data and parity — and a width
    that is not a whole number of blocks is refused."""
    rng = np.random.default_rng(2)
    n = 2 * BLOCK
    data = rng.integers(0, 256, (10, n), dtype=np.uint8)
    coder = PallasCoder(mm=mm, codec=codec)
    assert coder.fused_crc_ok
    parity, crcs = coder.encode_with_crc(data)
    parity, crcs = np.asarray(parity), np.asarray(crcs)
    assert np.array_equal(parity, NumpyCoder(codec=codec).encode(data))
    assert crcs.dtype == np.uint32 and crcs.shape == (14, 2)
    rows = np.concatenate([data, parity], axis=0)
    want = [[crc32c(rows[r, b * BLOCK:(b + 1) * BLOCK].tobytes())
             for b in range(2)] for r in range(rows.shape[0])]
    assert crcs.tolist() == want
    with pytest.raises(ValueError):
        coder.encode_with_crc(data[:, :BLOCK + 4096])


# ---------------------------------------------------------------------------
# The transfer layout: the chunk's bytes as (rows, n / 512, 128) words
# ---------------------------------------------------------------------------

RS_LOST = (3, 11)          # the rebuild cell's loss
DATA_LOST = (0, 1, 2, 3)   # the degraded read's: four data shards


@pytest.mark.parametrize("call,n", [
    pytest.param("encode", 2 * BLOCK, id="encode"),
    pytest.param("encode_crc", 2 * BLOCK, id="encode_crc"),
    pytest.param("rebuild", 16 * 4096, id="rebuild_3_11"),
    *[pytest.param("read", w, id=f"read_{w}")
      for w in (4 << 10, 16 << 10, 64 << 10, 256 << 10, 1 << 20)],
    pytest.param("encode", 1000, id="encode_padded"),
    pytest.param("rebuild", 5000, id="rebuild_padded"),
])
def test_word_layout_calls_match_numpy(call, n):
    """Every device call in the transfer layout against NumpyCoder: the
    fenced and the unfenced encode, with and without the fused CRCs
    (each the crc32c of its `.ecc` block, byte for byte), the rebuild's
    loss of shards 3 and 11, four lost data shards at every read width,
    and widths that are no whole number of blocks, which take ONE host
    pad a transfer and say so in `coder_layout`.  A drained handle is
    the bytes of the words that came back: a view, not a copy."""
    from seaweedfs_tpu.ops.coder_pallas import BLOCK_N
    from seaweedfs_tpu.ops.erasure import CODER_LAYOUT, READ_WIDTHS, host_rows
    if call == "read":
        assert n in READ_WIDTHS
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, (10, n), dtype=np.uint8)
    oracle = NumpyCoder(10, 4)
    full = oracle.encode_all(data)
    coder = PallasCoder(mm="int8")
    before = CODER_LAYOUT.counts()
    if call in ("encode", "encode_crc"):
        crc = call == "encode_crc"
        fenced = coder.encode_with_crc(data) if crc \
            else (coder.encode(data),)
        handles = coder.encode_unfenced(data, crc=crc)
        sends, want = 2, full[10:]
        got = [fenced[0], host_rows(handles[0])]
        if crc:
            want_crcs = [[crc32c(row[b * BLOCK:(b + 1) * BLOCK].tobytes())
                          for b in range(n // BLOCK)] for row in full]
            for h in (fenced, handles):
                assert np.asarray(h[1]).tolist() == want_crcs
    elif call == "rebuild":
        present = tuple(s for s in range(14) if s not in RS_LOST)[:10]
        handles = (coder.reconstruct_unfenced(
            present, np.ascontiguousarray(full[list(present)]), RS_LOST),)
        sends, want = 1, full[list(RS_LOST)]
        got = [host_rows(handles[0])]
    else:
        present = tuple(range(4, 14))
        handles = (coder.reconstruct_padded(
            present, np.ascontiguousarray(full[4:]), DATA_LOST),)
        sends, want = 1, full[:4]
        got = [host_rows(handles[0])]
    for rows in got:
        assert rows.dtype == np.uint8 and np.array_equal(rows, want)
    if n % BLOCK_N == 0:
        # the unfenced handle is the words themselves, drained in place
        assert handles[0].dtype == np.uint32
        assert handles[0].shape == (len(want), n // 512, 128)
        assert np.shares_memory(got[-1], np.asarray(handles[0]))
    after = CODER_LAYOUT.counts()
    padded = sends if n % BLOCK_N else 0
    assert after["words_in"] - before["words_in"] == sends
    assert after["padded"] - before["padded"] == padded
    assert after["padded_bytes"] - before["padded_bytes"] == \
        padded * 10 * -(-n // BLOCK_N) * BLOCK_N


def test_the_transfer_is_a_view_of_the_pooled_chunk(monkeypatch):
    """What crosses to the device is the chunk's own memory, viewed as
    words: no copy on the host, before or after the coder."""
    from seaweedfs_tpu.ec.encoder import CHUNK_POOL
    from seaweedfs_tpu.ops import coder_pallas
    coder = PallasCoder()
    sent = []
    real = coder_pallas.jax.device_put
    monkeypatch.setattr(coder_pallas.jax, "device_put",
                        lambda x, *a, **k: sent.append(x) or real(x, *a, **k))
    buf = CHUNK_POOL.take(10 * BLOCK)
    try:
        chunk = buf[:10 * BLOCK].reshape(10, BLOCK)
        chunk[:] = np.random.default_rng(5).integers(0, 256, chunk.shape,
                                                     dtype=np.uint8)
        (handle,) = coder.encode_unfenced(chunk)
        assert len(sent) == 1 and sent[0].dtype == np.uint32
        assert np.shares_memory(sent[0], buf)
        np.asarray(handle)
    finally:
        CHUNK_POOL.give(buf)


def test_int8_mm_correctness_gate():
    """Satellite: int8 is the on-TPU serving default (BENCH tuned it
    fastest) — gate it against the NumpyCoder oracle for encode AND
    reconstruct, rs and lrc."""
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (10, 8192), dtype=np.uint8)
    for codec in ("rs", "lrc"):
        oracle = NumpyCoder(codec=codec)
        c8 = PallasCoder(mm="int8", codec=codec)
        assert np.array_equal(np.asarray(c8.encode(data)),
                              oracle.encode(data))
        full = np.asarray(c8.encode_all(data))
        lost = (2, 11)
        have = {s: full[s] for s in range(full.shape[0])
                if s not in lost}
        got = c8.reconstruct(have, wanted=list(lost))
        for s in lost:
            assert np.array_equal(np.asarray(got[s]), full[s]), \
                (codec, s)


def test_int8_is_on_tpu_default(monkeypatch):
    from seaweedfs_tpu.ops import coder_pallas
    monkeypatch.setattr(coder_pallas, "_on_tpu", lambda: True)
    assert PallasCoder(interpret=True).mm == "int8"
    monkeypatch.setattr(coder_pallas, "_on_tpu", lambda: False)
    assert PallasCoder(interpret=True).mm == "bf16"


def test_write_ec_files_fused_matches_cpu_sidecar(tmp_path):
    """write_ec_files with the fused coder produces byte-identical
    shards AND a bit-identical `.ecc` to the CPU-accumulator path."""
    rng = np.random.default_rng(4)
    base_f = str(tmp_path / "1")
    base_c = str(tmp_path / "2")
    payload = rng.integers(0, 256, 2 * 1024 * 1024 + 999,
                           dtype=np.uint8).tobytes()
    for b in (base_f, base_c):
        with open(b + ".dat", "wb") as f:
            f.write(payload)
        with open(b + ".idx", "wb") as f:
            f.write(b"")
    write_ec_files(base_f, coder=PallasCoder(), chunk_size=BLOCK)
    write_ec_files(base_c, coder=NumpyCoder(), chunk_size=BLOCK)
    ecc_f = ShardChecksums.load(base_f)
    ecc_c = ShardChecksums.load(base_c)
    for sid in range(14):
        assert open(base_f + to_ext(sid), "rb").read() == \
            open(base_c + to_ext(sid), "rb").read()
        assert ecc_f.get(sid) == ecc_c.get(sid) == \
            file_block_crcs(base_f + to_ext(sid))


# ---------------------------------------------------------------------------
# Overlap regression — injected clock, structural, no sleeps
# ---------------------------------------------------------------------------

def test_pipeline_issues_next_h2d_before_prev_device_completes():
    """The streamed pipeline must dispatch chunk k+1 BEFORE chunk k's
    device step completes.  The fake device enforces it structurally:
    draining chunk k BLOCKS until dispatch(k+1) has been recorded —
    a serialized pipeline would deadlock here (bounded by timeout),
    the streamed one sails through."""
    counter = itertools.count()
    rec = PipelineRecorder(clock=lambda: next(counter))
    n_items = 6
    drained = []

    def drain(handle):
        if handle < n_items - 1:
            assert rec.wait_for("dispatched", handle + 1, timeout=30.0), \
                f"next H2D never issued while chunk {handle} in flight"
        drained.append(handle)

    n = run_pipeline(range(n_items), dispatch=lambda x: x, drain=drain,
                     depth=2, recorder=rec)
    assert n == n_items and drained == list(range(n_items))
    # Injected-clock ordering: the overlap is visible in the recorded
    # sequence numbers, not just in the absence of deadlock.
    for k in range(n_items - 1):
        assert rec.first_time("dispatched", k + 1) < \
            rec.first_time("drained", k)


def test_pipeline_error_paths_no_deadlock():
    with pytest.raises(RuntimeError, match="boom"):
        run_pipeline(range(100), dispatch=lambda x: x,
                     drain=lambda h: (_ for _ in ()).throw(
                         RuntimeError("boom")), depth=2)

    def gen():
        yield 1
        raise ValueError("genfail")
    with pytest.raises(ValueError, match="genfail"):
        run_pipeline(gen(), dispatch=lambda x: x,
                     drain=lambda h: None, depth=2)
    with pytest.raises(ZeroDivisionError):
        run_pipeline(range(10), dispatch=lambda x: 1 // 0,
                     drain=lambda h: None, depth=2)
    # Threads must not leak after error unwinds.
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ecpipe-")]


def test_scatter_byte_budget_caps_inflight():
    from seaweedfs_tpu.parallel.cluster_encode import _ByteBudget
    b = _ByteBudget(100)
    t1 = b.acquire(60)
    holder = {}

    def second():
        holder["taken"] = b.acquire(60)  # must block until release

    th = threading.Thread(target=second, daemon=True)
    th.start()
    th.join(timeout=0.2)
    assert th.is_alive() and "taken" not in holder
    b.release(t1)
    th.join(timeout=5.0)
    assert holder["taken"] == 60
    b.release(holder["taken"])
    # An oversized request is clamped, never deadlocks alone.
    big = b.acquire(10 ** 9)
    assert big == 100
    b.release(big)


def test_batch_encode_refuses_bad_chunk_size_before_freeze():
    """The chunk_size guard must reject every value _chunk_reader would
    choke on mid-stream — including in-range non-divisors of the large
    block — BEFORE any replica is frozen (env untouched: None works)."""
    from seaweedfs_tpu.ec import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE
    from seaweedfs_tpu.parallel.cluster_encode import batch_encode
    for bad in (SMALL_BLOCK_SIZE // 2, LARGE_BLOCK_SIZE * 2,
                3 * SMALL_BLOCK_SIZE):  # in range, !| large block
        with pytest.raises(ValueError):
            batch_encode(None, [], chunk_size=bad)


# ---------------------------------------------------------------------------
# shard_map batch step: zero collectives
# ---------------------------------------------------------------------------

def test_shard_map_batch_encode_zero_collectives():
    from seaweedfs_tpu.parallel.cluster_rebuild import make_mesh
    from seaweedfs_tpu.parallel.sharded_codec import assert_no_collectives

    mesh = make_mesh()
    hlo = assert_no_collectives(
        mesh, 4,
        (mesh.shape["vol"] * 2, 10, mesh.shape["col"] * 4096))
    assert hlo  # compiled and clean


# ---------------------------------------------------------------------------
# Wire-level: streamed batch encode golden equivalence + pushed .ecc
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    tmp_path = tmp_path_factory.mktemp("ecpipe")
    master = MasterServer(volume_size_limit_mb=64,
                          meta_dir=str(tmp_path), pulse_seconds=60)
    master.start()
    servers = []
    for i in range(3):
        d = tmp_path / f"vs{i}"
        d.mkdir()
        vs = VolumeServer(master.url(), [str(d)], pulse_seconds=60)
        vs.start()
        servers.append(vs)
    yield master, servers, tmp_path
    for vs in servers:
        vs.stop()
    master.stop()


def _freshen(servers):
    for vs in servers:
        vs._send_heartbeat(full=True)
        vs._ec_loc_cache.clear()


def _fill_ragged_volumes(master, n_volumes=2):
    """Volumes with deliberately unequal sizes so the streamed pipeline
    sees ragged tails: one volume runs out of chunks before the other
    (`active` shrinks mid-stream)."""
    from seaweedfs_tpu.cluster import rpc
    from seaweedfs_tpu.cluster.client import WeedClient
    client = WeedClient(master.url())
    rpc.call_json(f"{master.url()}/vol/grow?count={n_volumes}", "POST")
    rng = np.random.default_rng(7)
    by_vid: dict[int, int] = {}
    i = 0
    while len(by_vid) < n_volumes or min(by_vid.values()) < 4:
        payload = rng.integers(0, 256, 64 * 1024 + i * 37,
                               dtype=np.uint8).tobytes()
        fid = client.upload_data(payload)
        vid = int(fid.split(",")[0])
        by_vid[vid] = by_vid.get(vid, 0) + 1
        i += 1
        if i > 200:
            break
    return sorted(by_vid)[:n_volumes]


@pytest.mark.parametrize("codec", ["rs", "lrc"])
def test_streamed_batch_encode_golden(cluster, codec, tmp_path):
    """The overlapped pipeline's shard files AND holder `.ecc` sidecars
    are byte-identical to the seed `write_ec_files` golden layout plus
    the CPU crc32c reference — for ragged volume tails and both
    codecs.  Also proves receive_shard accepted the kernel-pushed CRCs
    (each holder's sidecar entry equals the reference without it ever
    reading the payload: the entries predate the shard push)."""
    from seaweedfs_tpu.cluster import rpc
    from seaweedfs_tpu.codecs import get_codec
    from seaweedfs_tpu.parallel.cluster_encode import batch_encode
    from seaweedfs_tpu.shell import CommandEnv

    master, servers, _ = cluster
    vids = _fill_ragged_volumes(master)
    env = CommandEnv(master.url())
    _freshen(servers)
    total = get_codec(codec).total_shards

    expect_dir = tmp_path / f"expected_{codec}"
    expect_dir.mkdir()
    expected: dict[int, dict[int, bytes]] = {}
    for vid in vids:
        url = env.volume_locations(vid)[0]
        base = str(expect_dir / str(vid))
        rpc.call_to_file(f"http://{url}/admin/volume_file?volume={vid}"
                         "&ext=.dat", base + ".dat")
        rpc.call_to_file(f"http://{url}/admin/volume_file?volume={vid}"
                         "&ext=.idx", base + ".idx")
        write_ec_files(base, coder=NumpyCoder(codec=codec),
                       codec=codec)
        write_sorted_file_from_idx(base)
        expected[vid] = {s: open(base + to_ext(s), "rb").read()
                         for s in range(total)}

    out = batch_encode(env, vids, chunk_size=BLOCK, codec=codec)
    for vid in vids:
        assert any(f"volume {vid} -> ec shards" in line
                   for line in out), out

    _freshen(servers)
    for vid in vids:
        locs = env.ec_shard_locations(vid)
        assert sorted(locs) == list(range(total))
        for sid in range(total):
            got = bytes(rpc.call(
                f"http://{locs[sid][0]}/admin/ec/shard_file?"
                f"volume={vid}&shard={sid}"))
            assert got == expected[vid][sid], (vid, sid)
    # Holder-side `.ecc`: every holder's sidecar entry for every local
    # shard file equals the CPU crc32c reference of its bytes, bit for
    # bit (filesystem walk of the fixture dirs — no server internals).
    _master, servers, base_tmp = cluster
    found = 0
    for root, _dirs, files in os.walk(base_tmp):
        for fname in files:
            m = re.match(r"^(\d+)\.ec(\d\d)$", fname)
            if not m or int(m.group(1)) not in vids:
                continue
            base = os.path.join(root, m.group(1))
            sid = int(m.group(2))
            ecc = ShardChecksums.load(base)
            want = file_block_crcs(os.path.join(root, fname))
            assert ecc.get(sid) == want, (base, sid)
            found += 1
    assert found >= total * len(vids)


def test_streamed_batch_rebuild_pushes_device_ecc(cluster):
    """Kill one shard of an encoded volume, batch-rebuild it, and
    check the new holder's `.ecc` entry matches the CPU crc32c of the
    rebuilt file byte-for-byte AND the rebuilt bytes are identical to
    the originals — the CRC fragment rode the scatter."""
    from seaweedfs_tpu.cluster import rpc
    from seaweedfs_tpu.parallel.cluster_rebuild import batch_rebuild
    from seaweedfs_tpu.shell import CommandEnv

    master, servers, base_tmp = cluster
    env = CommandEnv(master.url())
    _freshen(servers)
    vids = sorted({
        int(m.group(1))
        for root, _d, files in os.walk(base_tmp)
        for f in files
        for m in [re.match(r"^(\d+)\.ec03$", f)] if m})
    assert vids, "no encoded volumes (runs after the golden test)"
    vid = vids[0]
    holder = env.ec_shard_locations(vid)[3][0]
    original = bytes(rpc.call(
        f"http://{holder}/admin/ec/shard_file?volume={vid}&shard=3"))
    rpc.call_json(f"http://{holder}/admin/ec/delete_shards", "POST",
                  {"volume": vid, "shards": [3]})
    _freshen(servers)
    assert 3 not in env.ec_shard_locations(vid)

    out = batch_rebuild(env, [vid])
    assert any("rebuilt shards [3]" in line for line in out), out
    _freshen(servers)
    locs = env.ec_shard_locations(vid)
    assert 3 in locs
    rebuilt = bytes(rpc.call(
        f"http://{locs[3][0]}/admin/ec/shard_file?volume={vid}"
        "&shard=3"))
    assert rebuilt == original
    for root, _dirs, files in os.walk(base_tmp):
        if f"{vid}.ec03" in files:
            base = os.path.join(root, str(vid))
            crcs = ShardChecksums.load(base).get(3)
            if crcs is not None:
                assert crcs == file_block_crcs(base + ".ec03")
                return
    pytest.fail("rebuilt shard's .ecc entry not found")


def test_receive_ecc_endpoint_validation(cluster):
    from seaweedfs_tpu.cluster import rpc
    master, servers, _ = cluster
    url = servers[0].url()
    good = {"block": BLOCK, "shards": {"0": ["0a0b0c0d"]}}
    r = rpc.call(f"http://{url}/admin/ec/receive_ecc?volume=9999",
                 "POST", json.dumps(good).encode())
    assert r["merged"] is True
    with pytest.raises(rpc.RpcError) as ei:
        rpc.call(f"http://{url}/admin/ec/receive_ecc?volume=9999",
                 "POST", json.dumps(
                     {"block": BLOCK, "shards": {"99": ["00000000"]}}
                 ).encode())
    assert ei.value.status == 400
    with pytest.raises(rpc.RpcError) as ei:
        rpc.call(f"http://{url}/admin/ec/receive_ecc?volume=9999",
                 "POST", b"not json")
    assert ei.value.status == 400
    # Wrong shapes must 400, not 500 — and a bare hex string must not
    # be char-iterated into bogus one-digit CRCs.
    for bad in ({"block": BLOCK, "shards": []},
                {"block": BLOCK, "shards": "0a0b0c0d"},
                {"block": BLOCK, "shards": {"0": "0a0b0c0d"}},
                # >32-bit / negative values can never equal a
                # recomputed crc32c — merged, they'd make the first
                # scrub quarantine a healthy shard.
                {"block": BLOCK, "shards": {"0": ["1aabbccdd"]}},
                {"block": BLOCK, "shards": {"0": ["-1"]}}):
        with pytest.raises(rpc.RpcError) as ei:
            rpc.call(f"http://{url}/admin/ec/receive_ecc?volume=9999",
                     "POST", json.dumps(bad).encode())
        assert ei.value.status == 400, bad
    # Existing entries survive a merge of other shards.
    more = {"block": BLOCK, "shards": {"1": ["11111111"]}}
    rpc.call(f"http://{url}/admin/ec/receive_ecc?volume=9999",
             "POST", json.dumps(more).encode())
    base = servers[0]._volume_base(9999)
    ecc = ShardChecksums.load(base)
    assert ecc.get(0) == [0x0a0b0c0d] and ecc.get(1) == [0x11111111]


def test_receive_shard_stale_ecc_refingerprinted(cluster):
    """receive_shard only trusts a `.ecc` entry that receive_ecc
    shipped for THIS push (the pending map).  A stale sidecar entry
    left by a prior encode generation — same padded shard size, so the
    block count matches — must be re-fingerprinted from the pushed
    body, or the first scrub would quarantine a healthy shard."""
    from seaweedfs_tpu.cluster import rpc
    master, servers, _ = cluster
    vs = servers[0]
    url = vs.url()
    vid = 9998
    body = bytes(np.random.default_rng(7).integers(
        0, 256, BLOCK, dtype=np.uint8))
    true_crc = crc32c(body)
    stale = (true_crc + 1) & 0xFFFFFFFF

    # A prior generation's entry: in the sidecar, NOT pending.
    rpc.call(f"http://{url}/admin/ec/receive_ecc?volume={vid}", "POST",
             json.dumps({"block": BLOCK,
                         "shards": {"3": [f"{stale:08x}"]}}).encode())
    vs._ec_pending_ecc.clear()  # the pushing encoder is long gone
    rpc.call(f"http://{url}/admin/ec/receive_shard?volume={vid}"
             "&shard=3", "POST", body)
    base = vs._volume_base(vid)
    assert ShardChecksums.load(base).get(3) == [true_crc]

    # Fresh fragment for this push: consumed from the pending map and
    # trusted verbatim — it describes the INTENDED bytes, so a CRC that
    # differs from the wire body is exactly what makes push corruption
    # scrub-detectable (no CPU re-fingerprint overwrites it).
    intended = (true_crc ^ 0xDEADBEEF) & 0xFFFFFFFF
    rpc.call(f"http://{url}/admin/ec/receive_ecc?volume={vid}", "POST",
             json.dumps({"block": BLOCK,
                         "shards": {"4": [f"{intended:08x}"]}}).encode())
    rpc.call(f"http://{url}/admin/ec/receive_shard?volume={vid}"
             "&shard=4", "POST", body)
    assert ShardChecksums.load(base).get(4) == [intended]
    assert vid not in vs._ec_pending_ecc  # consumed, not leaked

    # An EXPIRED pending entry (its shard push failed long ago, and a
    # later generation's push happens to match the block count) must
    # not be trusted either: fingerprint wins.
    from seaweedfs_tpu.cluster import volume_server as vs_mod
    rpc.call(f"http://{url}/admin/ec/receive_ecc?volume={vid}", "POST",
             json.dumps({"block": BLOCK,
                         "shards": {"5": [f"{stale:08x}"]}}).encode())
    old_ttl = vs_mod._PENDING_ECC_TTL
    vs_mod._PENDING_ECC_TTL = 0.0
    try:
        rpc.call(f"http://{url}/admin/ec/receive_shard?volume={vid}"
                 "&shard=5", "POST", body)
    finally:
        vs_mod._PENDING_ECC_TTL = old_ttl
    assert ShardChecksums.load(base).get(5) == [true_crc]
