"""The served EC file pipeline's stage clock (stats/roofline.py
StageClock, ec/encoder.py, the EC admin handlers): a closed catalog, one
flag check when disarmed, contiguous main-thread stages that sum to the
job's wall, rows on /debug/device beside (never among) the kernel rows,
the job's totals on the finish events and the admin request's span, and
the annotated stages on the profiler's host plane.

Marker: roofline (tier-1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import _stage_drive
from seaweedfs_tpu.ec import SMALL_BLOCK_SIZE, to_ext
from seaweedfs_tpu.ec.encoder import rebuild_ec_files, write_ec_files
from seaweedfs_tpu.ec.integrity import ShardChecksums, file_block_crcs
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.ops.coder_pallas import PallasCoder
from seaweedfs_tpu.shell import CommandEnv, run_command
from seaweedfs_tpu.stats import roofline
from seaweedfs_tpu.stats.roofline import ANNOTATED_STAGES, STAGES, StageClock

pytestmark = pytest.mark.roofline

BLOCK = SMALL_BLOCK_SIZE
COUNTED_ONLY = {"seal.stack", "seal.dispatch", "seal.drain",
                "rebuild.dispatch", "rebuild.drain", "beside.rebuild_read",
                "beside.seal_write", "req.beside_job", "req.alone",
                "read.dispatch", "read.drain", "read.interval",
                "read.degraded", "read.healthy"}
# the read-ahead and writer threads' rows: beside the main thread, in
# no sum
BESIDE = {"seal.stack", "beside.rebuild_read", "beside.seal_write"}
# the drives answer their one upload before any job starts
DRIVEN = set(STAGES) - {"req.beside_job"}


@pytest.fixture(autouse=True)
def _clean_ledger():
    roofline.LEDGER.reset()
    yield
    roofline.set_armed(True)
    roofline.LEDGER.reset()


def _volume(tmp_path, name: str, nbytes: int) -> str:
    base = str(tmp_path / name)
    rng = np.random.default_rng(7)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    with open(base + ".idx", "wb"):
        pass
    return base


# -- (a) the catalog and the switch -------------------------------------------

def test_stage_catalog_is_closed():
    assert ANNOTATED_STAGES == set(STAGES) - COUNTED_ONLY
    assert not set(STAGES) & set(roofline.KERNELS)
    clock = StageClock("rs")
    with pytest.raises(ValueError, match="unknown pipeline stage"):
        clock("seal.bogus")
    with clock("seal.drain", 3) as st:
        st.add_bytes(4)
    with clock("seal.drain"):
        pass
    got = clock.totals()
    assert list(got) == ["seal.drain"]
    assert got["seal.drain"]["count"] == 2
    assert got["seal.drain"]["bytes"] == 7
    rows = roofline.LEDGER.stage_table()
    assert [(r["kernel"], r["codec"], r["count"], r["bytes"])
            for r in rows] == [("seal.drain", "rs", 2, 7)]
    assert set(rows[0]) == {"kernel", "codec", "count", "seconds",
                            "bytes"}


def test_disarmed_stage_clock_is_one_flag_check(tmp_path, monkeypatch):
    """-roofline=false: a booby-trapped stage and ledger prove that no
    stage is built, timed, annotated or recorded, and the pipeline
    still writes its files."""
    def boom(*a, **k):
        raise AssertionError("stage clock reached while disarmed")

    monkeypatch.setattr(roofline, "_Stage", boom)
    monkeypatch.setattr(roofline.RooflineLedger, "add_stage", boom)
    roofline.set_armed(False)
    clock = StageClock("rs")
    with clock("not even looked up") as st:
        st.add_bytes(1)
    base = _volume(tmp_path, "1", BLOCK + 99)
    write_ec_files(base, coder=NumpyCoder(), clock=clock)
    os.remove(base + to_ext(3))
    assert rebuild_ec_files(base, coder=NumpyCoder(), clock=clock) == [3]
    assert clock.totals() == {} and not roofline.LEDGER.stage_table()


# -- (b) the stages of one job are contiguous and do not nest ----------------

def test_main_thread_stages_sum_to_the_wall(tmp_path, monkeypatch):
    """A seal and a rebuild of a small volume with the Pallas coder
    (interpret mode), built before the clock starts: the main-thread
    stages sum to 90-100 % of the function's wall and never to more,
    every per-chunk row counts the chunks, the drain's bytes are the
    parity and CRC bytes brought back, and the files are what the CPU
    path writes."""
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", "1")
    chunks = 3
    n = chunks * BLOCK                      # bytes of one shard
    base = _volume(tmp_path, "1", 10 * n - 12345)
    want = _volume(tmp_path, "2", 10 * n - 12345)
    write_ec_files(want, coder=NumpyCoder(), chunk_size=BLOCK)
    coder = PallasCoder()
    warm = np.zeros((10, BLOCK), np.uint8)
    np.asarray(coder.encode_with_crc(warm)[0])      # compile outside
    np.asarray(coder.reconstruct_unfenced(
        [s for s in range(12) if s not in (3, 11)], warm, [3, 11]))
    roofline.LEDGER.reset()

    clock = StageClock("rs")
    t0 = time.perf_counter()
    write_ec_files(base, coder=coder, chunk_size=BLOCK, clock=clock)
    wall = time.perf_counter() - t0
    got = clock.totals()
    main = sum(v["seconds"] for k, v in got.items() if k not in BESIDE)
    assert 0.90 * wall <= main <= wall, (main, wall, got)
    for stage in ("seal.dispatch", "seal.write_data", "seal.drain"):
        assert got[stage]["count"] == chunks, stage
    # one more wait than chunks (the end of the stream; the writers'
    # last rows), a read a chunk, a write a row
    assert got["seal.stack_wait"]["count"] == chunks + 1
    assert got["seal.write_parity"]["count"] == chunks + 1
    assert got["beside.seal_write"]["count"] == 14 * chunks
    assert got["beside.seal_write"]["bytes"] == 14 * n
    assert got["seal.stack"]["count"] == chunks
    assert got["seal.stack"]["bytes"] == 10 * n
    assert got["seal.dispatch"]["bytes"] == 10 * n
    assert got["seal.write_data"]["bytes"] == 10 * n
    assert got["seal.write_parity"]["bytes"] == 4 * n
    assert got["seal.drain"]["bytes"] == 4 * n + 14 * chunks * 4
    assert got["seal.finish"]["count"] == 2
    ecc, ecc_want = ShardChecksums.load(base), ShardChecksums.load(want)
    for sid in range(14):
        with open(base + to_ext(sid), "rb") as a, \
                open(want + to_ext(sid), "rb") as b:
            assert a.read() == b.read(), sid
        assert ecc.get(sid) == ecc_want.get(sid) == \
            file_block_crcs(base + to_ext(sid))

    for sid in (3, 11):
        os.remove(base + to_ext(sid))
    clock = StageClock("rs")
    t0 = time.perf_counter()
    assert rebuild_ec_files(base, coder=coder, chunk_size=BLOCK,
                            clock=clock) == [3, 11]
    wall = time.perf_counter() - t0
    got = clock.totals()
    assert set(got) == {"rebuild.read", "rebuild.dispatch",
                        "rebuild.drain", "rebuild.write",
                        "rebuild.finish", "beside.rebuild_read"}
    main = sum(v["seconds"] for k, v in got.items() if k not in BESIDE)
    assert 0.90 * wall <= main <= wall, (main, wall, got)
    # a chunk is dispatched, drained and written once, whole
    for stage in ("rebuild.dispatch", "rebuild.drain", "rebuild.write",
                  "beside.rebuild_read"):
        assert got[stage]["count"] == chunks, stage
    # one more wait than chunks (the end of the stream)
    assert got["rebuild.read"]["count"] == chunks + 1
    assert got["rebuild.read"]["bytes"] == 0
    assert got["beside.rebuild_read"]["bytes"] == 10 * n
    assert got["rebuild.dispatch"]["bytes"] == 10 * n
    assert got["rebuild.drain"]["bytes"] == 2 * n
    assert got["rebuild.write"]["bytes"] == 2 * n
    for sid in (3, 11):
        with open(base + to_ext(sid), "rb") as a, \
                open(want + to_ext(sid), "rb") as b:
            assert a.read() == b.read(), sid
    assert ShardChecksums.load(base).get(3) == ecc_want.get(3)
    # both jobs fed the process rows too, under their codec
    rows = {r["kernel"]: r for r in roofline.LEDGER.stage_table()}
    assert rows["seal.drain"]["count"] == chunks
    assert rows["rebuild.write"]["codec"] == "rs"


def test_a_failed_chunk_still_closes_its_stage(tmp_path):
    """The clock is a `with`: a coder that raises leaves no stage open
    and the reader thread is joined."""
    class Broken(NumpyCoder):
        def encode(self, data):
            raise RuntimeError("device lost")

    base = _volume(tmp_path, "1", 2 * BLOCK)
    clock = StageClock("rs")
    with pytest.raises(RuntimeError, match="device lost"):
        write_ec_files(base, coder=Broken(), clock=clock)
    got = clock.totals()
    assert got["seal.dispatch"]["count"] == 1
    assert "seal.drain" not in got and got["seal.finish"]["count"] == 1


# -- the operator's view ------------------------------------------------------------

def test_stage_rows_ride_debug_device_events_and_the_span(
        tmp_path, monkeypatch):
    """Through the volume server's own handlers: one row per stage in
    /debug/device's `kernels` (what benchmark/served.py sums by name),
    none of them in the heartbeat's rollup or cluster.roofline's kernel
    table, and the job's totals on the finish event and on the admin
    request's server span (recorded with SEAWEEDFS_TPU_TRACES=1), set
    once."""
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACES", "1")
    got = _stage_drive.drive(str(tmp_path))
    assert sorted(got["rebuilt"]) == _stage_drive.LOST
    rows = [r for r in got["device"]["kernels"] if r["kernel"] in STAGES]
    assert {r["kernel"] for r in rows} == DRIVEN
    for r in rows:
        assert set(r) == {"kernel", "codec", "count", "seconds", "bytes"}
        assert r["codec"] == ("" if r["kernel"] == "req.alone"
                              else "rs") and r["count"] >= 1
    # the handlers name the mounts by what they end
    by = {r["kernel"]: r for r in rows}
    assert by["seal.mount"]["count"] == 1
    assert by["rebuild.mount"]["count"] == 1
    assert by["seal.delete_original"]["count"] == 1
    # kernel surfaces are the kernels' alone
    hb = roofline.LEDGER.heartbeat_view()
    assert not [r for r in hb["kernels"] if r["kernel"] in STAGES]
    assert not [r for r in got["device"]["recent"]
                if r["kernel"] in STAGES]
    for kind, prefix, route in (
            ("ec.encode.finish", "seal.", "/admin/ec/generate"),
            ("ec.rebuild.finish", "rebuild.", "/admin/ec/rebuild")):
        stages = got["finish"][kind]["attrs"]["stages"]
        assert stages and all(s.startswith(prefix) or s in BESIDE
                              for s in stages)
        assert f"{prefix}drain" in stages
        assert all(set(v) == {"count", "seconds", "bytes"}
                   for v in stages.values())
        span = [s for s in got["spans"][kind] if route in s["name"]]
        assert len(span) == 1 and span[0]["attrs"]["stages"] == stages
    json.dumps(got["finish"])          # the journal's sink writes JSON


def test_cluster_roofline_prints_stages_in_a_section_of_their_own(
        tmp_path, monkeypatch):
    from seaweedfs_tpu.cluster import rpc
    clock = StageClock("rs")
    with clock("seal.write_data", 10):
        pass
    PallasCoder(4, 2).encode(np.ones((4, 2048), np.uint8))
    doc = roofline.debug_doc("n:1", "volume")
    monkeypatch.setattr(rpc, "call", lambda url, **kw: doc)
    out = run_command(CommandEnv("http://127.0.0.1:1"),
                      "cluster.roofline -node n:1")
    head, _, tail = out.partition("EC file pipeline stages")
    assert "encode_kernel" in head and "seal.write_data" not in head
    assert "seal.write_data" in tail and "encode_kernel" not in tail
    assert "seal.stack host buffers: " in tail and " MiB held" in tail
    assert "seal.drain: " in tail and " waited for" in tail
    assert "seal.write_data: " in tail and " waited for them" in tail
    assert "rebuild.drain: " in tail


def test_debug_device_serves_seal_inflight(tmp_path, monkeypatch):
    """Through the volume server's own handlers: `/debug/device` says,
    beside `seal_buffers`, how the seal's drains found the oldest chunk
    in flight, one count per drained chunk."""
    from seaweedfs_tpu.ec import encoder
    monkeypatch.setattr(encoder, "SEAL_INFLIGHT", encoder._InflightCount())
    got = _stage_drive.drive(str(tmp_path))["device"]
    drains = next(r for r in got["kernels"] if r["kernel"] == "seal.drain")
    assert set(got["seal_inflight"]) == {"ready", "waited"}
    assert sum(got["seal_inflight"].values()) == drains["count"] >= 1
    assert set(got["seal_buffers"]) == {"reused", "allocated",
                                        "held_bytes"}
    # ... and how the hand-overs found the writer threads, whose rows
    # are one row of the same list
    assert set(got["seal_writer"]) == {"ready", "waited"}
    assert sum(got["seal_writer"].values()) >= drains["count"]
    writes = next(r for r in got["kernels"]
                  if r["kernel"] == "beside.seal_write")
    assert writes["count"] == 14 * drains["count"]
    # ... and which way a GET's shard reads go in this process
    from seaweedfs_tpu.ec.volume import read_many_path
    assert got["ec_reads"] == read_many_path()
    assert got["ec_reads"] in ("sw_pread_rows", "preadv")


def test_debug_device_serves_rebuild_inflight(tmp_path, monkeypatch):
    """... and, beside `seal_inflight`, the same for the rebuild's
    drains: one count per drained chunk of the driven rebuild."""
    from seaweedfs_tpu.ec import encoder
    monkeypatch.setattr(encoder, "REBUILD_INFLIGHT",
                        encoder._InflightCount())
    got = _stage_drive.drive(str(tmp_path))["device"]
    drains = next(r for r in got["kernels"]
                  if r["kernel"] == "rebuild.drain")
    assert set(got["rebuild_inflight"]) == {"ready", "waited"}
    assert sum(got["rebuild_inflight"].values()) == drains["count"] >= 1
    beside = next(r for r in got["kernels"]
                  if r["kernel"] == "beside.rebuild_read")
    assert beside["count"] == drains["count"]


# -- (c) the annotated stages on the profiler's clock ---------------------------

def test_annotated_stages_are_host_events_of_the_trace(tmp_path):
    """Under `jax.profiler.start_trace` on the CPU platform (a child
    with a time limit of its own: a profiler session is the process's),
    the `.xplane.pb` read with benchmark/tracing.py `load()` has host
    events named for every annotated stage, as many as the row counts
    and as long as the row says, and none for the stages that are
    counted only."""
    p = subprocess.run(
        [sys.executable, _stage_drive.__file__, str(tmp_path)],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.splitlines()[-1])
    events, rows = got["events"], got["rows"]
    assert set(events) == ANNOTATED_STAGES
    assert set(rows) == DRIVEN
    for name, (count, seconds) in events.items():
        assert count == rows[name]["count"], name
        # one clock read apart on each side: microseconds a stage
        assert rows[name]["seconds"] <= seconds + 1e-6, name
        assert seconds - rows[name]["seconds"] <= \
            0.02 * seconds + 200e-6 * count, name
