"""Repairing while serving (PERF.md, configuration `live-ec-repair`):
one process answers needle requests and rebuilds lost shards.

- a served rebuild of a 40 MiB volume without shards 3 and 11, through
  the admin shell, while client threads write 1 KB needles into other
  volumes of the same server: the rebuilt shards are the kept ones byte
  for byte, `.ecc` is the plain reference's (benchmark/ecref.py), every
  acknowledged write reads back, no request failed, some were booked
  beside the job, the chunk pool holds what it held, and no handler
  walked the data directory;
- a rebuild that raises under the same load takes the job mark down,
  leaves no reader thread and no buffer behind, and the clients go on;
- a mounted EC volume's files are found without a walk of the data
  directory (the rebuild and the mount after it ran two, 0.3 s each
  beside sixteen request threads), loose shards still by the walk;
- the three `load_rebuild_*` readers of benchmark/metrics/ on facts made
  by hand, and the manifest's new tail.

All in process on the CPU platform (the Pallas coder in interpret mode
where a coder runs), in ONE file: under `--dist loadfile` one worker runs
them one after another.  Every wait is for a state, with a deadline.
"""

import glob
import os
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_seal_under_load import (DEADLINE, MIB, Writers,  # noqa: E402
                                  cluster, wait_for)

from benchmark import ecjobs, ecref, manifest  # noqa: E402
from benchmark.data import Http, request_payload  # noqa: E402
from seaweedfs_tpu.cluster import rpc  # noqa: E402
from seaweedfs_tpu.ec import encoder  # noqa: E402
from seaweedfs_tpu.shell import CommandEnv, run_command  # noqa: E402
from seaweedfs_tpu.stats import roofline  # noqa: E402

__all__ = ["cluster"]           # the sibling file's fixture, used here
LOST = [3, 11]
BESIDE = "req.beside_job"


@pytest.fixture(autouse=True)
def _clean_ledger():
    roofline.LEDGER.reset()
    yield
    roofline.set_armed(True)
    roofline.LEDGER.reset()


def stage_rows() -> dict:
    return {r["kernel"]: r for r in roofline.LEDGER.stage_table()}


def counted_walks(monkeypatch) -> list:
    """Every pattern `glob.glob` is asked for from here on."""
    asked, walk = [], glob.glob

    def counting(pattern, *a, **k):
        asked.append(pattern)
        return walk(pattern, *a, **k)
    monkeypatch.setattr(glob, "glob", counting)
    return asked


def pipeline_threads() -> list:
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ec-read-ahead", "ec-rebuild-read"))]


def sealed_and_damaged(cluster, seed: int, env) -> ecjobs.Volume:
    """A 40 MiB volume of collection `pool`, sealed through the shell,
    its 14 shards kept, then shards 3 and 11 lost the way a disk loses
    them; 3 volumes of collection `bench` for the clients."""
    tpl = ecjobs.fill_volume(cluster, seed, 21, "pool", 40 * MIB,
                             (4096, MIB))
    run_command(env, "lock")
    out = run_command(env, f"ec.encode -volumeId {tpl.vid}")
    assert f"volume {tpl.vid} -> ec shards" in out
    ecjobs.keep_shards(cluster, tpl)
    ecjobs.lose(cluster, [tpl.vid], LOST)
    assert ecjobs.files_missing(tpl.base, tpl) == len(LOST)
    rpc.call(f"{cluster.master}/vol/grow?count=3&collection=bench", "POST")
    return tpl


def assert_rebuilt_like_the_kept(cluster, tpl, seed: int) -> None:
    """The benchmark's own comparison of a rebuilt volume, every number
    0, and `.ecc` of the rebuilt shards against the kept files' CRCs."""
    got = ecjobs.compare_shards(tpl.base, tpl, seed, tpl.vid, 4,
                                shards=LOST)
    assert got == {"files_missing": 0, "data_blocks_differ": 0,
                   "parity_blocks_differ": 0, "ecc_entries_differ": 0}
    crcs = ecref.load_ecc(tpl.base)
    for sid in LOST:
        kept = os.path.join(tpl.shard_dir, "shard" + ecref.ext(sid))
        assert ecjobs.files_differ(tpl.base + ecref.ext(sid), kept) == 0
        assert list(crcs[sid]) == list(ecref.file_block_crcs(kept)), sid
    assert ecjobs.compare_needles(cluster, tpl, seed, tpl.vid, 4)[
        "needles_differ"] == 0


def assert_every_write_reads_back(cluster, writers, acked) -> None:
    assert not writers.failed
    http = Http(cluster.master)
    try:
        for ident, fid, url in acked:
            assert http.read(url, fid) == request_payload(
                writers.block, ident, 1024), fid
    finally:
        http.close()


# -- a served rebuild under concurrent writes --------------------------------

def test_served_rebuild_under_concurrent_writes_is_the_kept_shards(
        cluster, monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_CODER", "pallas")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", "1")
    seed = 2**31 + 32
    env = CommandEnv(cluster.master)
    tpl = sealed_and_damaged(cluster, seed, env)
    held = encoder.CHUNK_POOL.counts()["held_bytes"]
    roofline.LEDGER.reset()
    writers = Writers(cluster.master, seed, clients=4)
    walks = counted_walks(monkeypatch)
    try:
        writers.each_wrote(5)                 # the load is on
        out = run_command(env, f"ec.rebuild -volumeId {tpl.vid}")
        assert f"volume {tpl.vid}: rebuilt shards {LOST}" in out
        writers.each_wrote(5)                 # and outlasts the rebuild
    finally:
        acked = writers.stop()
        env.close()

    assert_rebuilt_like_the_kept(cluster, tpl, seed)
    assert acked and all(n >= 10 for n in writers.done())
    assert tpl.vid not in {int(fid.split(",")[0]) for _i, fid, _u in acked}
    assert_every_write_reads_back(cluster, writers, acked)

    # the rows: uploads beside the rebuild, its stages under its codec,
    # and nothing kept: no mark, no thread, no buffer out of the pool;
    # neither handler looked for the mounted volume's files on the disk
    rows = stage_rows()
    assert rows[BESIDE]["count"] >= 1
    assert rows["rebuild.dispatch"]["count"] == \
        rows["beside.rebuild_read"]["count"] == 1
    assert rows["rebuild.mount"]["count"] == 1
    assert roofline.jobs_running() == 0
    assert not pipeline_threads()
    assert encoder.CHUNK_POOL.counts()["held_bytes"] >= held > 0
    assert walks == []


def test_a_rebuild_that_raises_under_load_leaves_nothing_behind(
        cluster, monkeypatch):
    """The disk is full at the first write of a rebuilt row, beside four
    writing clients: the admin request answers with the error, the mark
    comes down, the read-ahead thread and its readers are gone, the
    pool holds its buffers (the chunk was drained: nothing may still
    read it), the clients never notice — and once the half-written
    shards are deleted the same volume rebuilds."""
    monkeypatch.setenv("SEAWEEDFS_TPU_CODER", "pallas")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", "1")
    seed = 2**31 + 33
    env = CommandEnv(cluster.master)
    tpl = sealed_and_damaged(cluster, seed, env)
    held = encoder.CHUNK_POOL.counts()["held_bytes"]

    def full_disk(f, sid, buf, accs):
        raise OSError(28, "No space left on device")

    roofline.LEDGER.reset()
    writers = Writers(cluster.master, seed, clients=4)
    try:
        writers.each_wrote(5)
        with monkeypatch.context() as m:
            m.setattr(encoder, "_shard_write", full_disk)
            with pytest.raises(rpc.RpcError, match="No space left"):
                rpc.call_json(f"{cluster.volume}/admin/ec/rebuild", "POST",
                              {"volume": tpl.vid})
        assert roofline.jobs_running() == 0
        wait_for("the pipeline's threads gone",
                 lambda: not pipeline_threads(), 10.0)
        assert encoder.CHUNK_POOL.counts()["held_bytes"] >= held > 0
        writers.each_wrote(5)                 # the clients go on
        # what the failed job left of 3 and 11 goes the operator's way
        ecjobs.lose(cluster, [tpl.vid], LOST)
        out = run_command(env, f"ec.rebuild -volumeId {tpl.vid}")
        assert f"volume {tpl.vid}: rebuilt shards {LOST}" in out
    finally:
        acked = writers.stop()
        env.close()
    assert_rebuilt_like_the_kept(cluster, tpl, seed)
    assert_every_write_reads_back(cluster, writers, acked)
    rows = stage_rows()
    assert rows["rebuild.write"]["count"] == 2    # the one that raised too
    assert roofline.jobs_running() == 0 and not pipeline_threads()


# -- where a volume's files are ------------------------------------------------

def test_a_mounted_ec_volume_is_found_without_a_walk_loose_shards_by_one(
        cluster, monkeypatch):
    """`_volume_base` answers from the store, then from the mounted EC
    volumes, and only then walks the data directory: for shards that
    lie there unmounted (a copy that has not been mounted yet), and
    never taking an in-flight temp file for a shard."""
    seed = 2**31 + 34
    env = CommandEnv(cluster.master)
    try:
        tpl = sealed_and_damaged(cluster, seed, env)
    finally:
        env.close()
    vs = cluster.vs
    walks = counted_walks(monkeypatch)
    bench_vid = next(v.vid for loc in vs.store.locations
                     for v in loc.volumes.values()
                     if v.collection == "bench")
    assert vs._volume_base(bench_vid) == \
        vs.store.find_volume(bench_vid).file_name()
    assert vs.store.find_volume(tpl.vid) is None      # sealed: shards only
    assert vs._volume_base(tpl.vid) == tpl.base
    assert walks == []
    # unmounted, the shards are loose files: the walk finds the same base
    rpc.call_json(f"{cluster.volume}/admin/ec/unmount", "POST",
                  {"volume": tpl.vid})
    assert tpl.vid not in vs.ec_volumes
    open(tpl.base + ".ec03.part", "wb").close()       # a copy in flight
    assert vs._volume_base(tpl.vid) == tpl.base
    assert walks
    # mounted again it rebuilds, with no walk from then on
    os.remove(tpl.base + ".ec03.part")
    rpc.call_json(f"{cluster.volume}/admin/ec/mount", "POST",
                  {"volume": tpl.vid})
    del walks[:]
    got = rpc.call_json(f"{cluster.volume}/admin/ec/rebuild", "POST",
                        {"volume": tpl.vid})
    assert got["rebuilt_shards"] == LOST
    got = rpc.call_json(f"{cluster.volume}/admin/ec/mount", "POST",
                        {"volume": tpl.vid})
    assert got["shards"] == list(range(14))
    assert walks == []
    assert_rebuilt_like_the_kept(cluster, tpl, seed)


def test_a_volume_nobody_holds_gets_the_first_directory_s_name(cluster):
    vs = cluster.vs
    assert vs._volume_base(999) == os.path.join(cluster.data_dir, "999")


# -- the readers ------------------------------------------------------------------

MAN = manifest.load()
CELL = "rebuild-under-load"
ROWS = {"rebuild.read": (168, 0.5), "rebuild.dispatch": (156, 1.0),
        "rebuild.drain": (156, 0.25), "rebuild.write": (156, 1.37),
        "rebuild.finish": (24, 0.5), "rebuild.mount": (12, 0.38),
        "beside.rebuild_read": (156, 1.56),
        "req.beside_job": (3000, 60.0), "req.alone": (5000, 50.0)}
WANT = {"load_rebuild_share": 40.0,            # 4.0 s of 10, no beside.
        "load_rebuild_ms_per_chunk": 20.0,     # 3.12 s over 156 chunks
        "load_rebuild_reader_ms_per_chunk": 10.0}
# `moves` is nominal (the readers' docstrings say why): the cell cannot
# list `rebuild_MBps`, and an entry names a rate its cells report
MOVES, PER_SECOND = "req_per_s", 1.2
ROW_OF = {"load_rebuild_share": "rebuild.",
          "load_rebuild_ms_per_chunk": "rebuild.",
          "load_rebuild_reader_ms_per_chunk": "beside."}


def facts(rows: dict, op: str = "ec.rebuild") -> dict:
    return {"window_s": 10.0, "trace": None,
            "requests": {"op": "write", "attempted": 8000, "failed": 0},
            "jobs": {"op": op, "count": 12, "shard_bytes": 1, "lost": 2},
            "coder_rows": {k: {"count": c, "seconds": s, "bytes": 1}
                           for k, (c, s) in rows.items()}}


@pytest.mark.parametrize("name,want", WANT.items())
def test_load_rebuild_readers_on_facts_made_by_hand(name, want):
    read = manifest.reader(MAN, name)
    assert read(facts(ROWS)) == pytest.approx(want)
    # a program without the rows this metric reads (a commit before
    # PR 31 serves no `beside.rebuild_read`): left out of the line,
    # never a 0; and a seal's window has none of them
    without = {k: v for k, v in ROWS.items()
               if not k.startswith(ROW_OF[name])}
    assert read(facts(without)) is None
    assert read(facts({})) is None
    assert read(facts(ROWS, op="ec.encode")) is None
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "%" if name.endswith("_share") else "ms",
        "better": "lower", "source": "program_span",
        "layer": "EC file pipeline", "moves": MOVES, "workloads": [CELL]}


def test_the_new_cell_is_entries_appended_and_files_added():
    """PR 32's entries come after everything PR 27 left, in this order
    and side by side (read by membership: later PRs retired entries
    ahead of them and appended others behind), and the cell reports
    what the issue lists for it."""
    assert [c["name"] for c in MAN["configs"]][3:4] == ["live-ec-repair"]
    assert [w for w in MAN["workloads"]][4:5] == [{
        "name": CELL, "config": "live-ec-repair",
        "traffic": "write-1k-rebuilding", "chips": 4,
        "why": MAN["workloads"][4]["why"]}]
    # nothing here exists only across chips: the cell holds the whole
    # host because on one chip the driver's check read its rate wider
    # than the bound (PERF.md, Findings, PR 32, round 3), and says so
    assert "4 chips for steadiness alone" in MAN["workloads"][4]["why"]
    # ... as `seal` does since PR 34, and no other cell
    assert {w["name"] for w in MAN["workloads"] if w["chips"] == 4} == {
        "seal", CELL}
    names = [m["name"] for m in MAN["per_layer"]]
    first = names.index(next(iter(WANT)))
    assert names[first:first + len(WANT)] == list(WANT)
    assert first > names.index("load_req_beside_share")
    cell = manifest.cell(MAN, CELL)
    # the `rebuild` cell's jobs to the letter
    assert cell["traffic"]["jobs"] == manifest.cell(
        MAN, "rebuild")["traffic"]["jobs"] == {
        "op": "ec.rebuild", "per_second": PER_SECOND,
        "metric": "rebuild_MBps"}
    assert cell["traffic"]["requests"] == manifest.cell(
        MAN, "bench-write-1k")["traffic"]["requests"] == {
        "op": "write", "warm_seconds": 3}
    assert "ec" not in cell["traffic"]
    # both parents' values, unchanged, and every guarantee of either
    for parents_cell in ("rebuild", "bench-write-1k"):
        src = manifest.cell(MAN, parents_cell)["config"]
        for key, value in src.items():
            # `server_env` is the parents' servers' allocator (PR 34),
            # which this configuration does not take over (PERF.md §7)
            if key in ("name", "source", "deployment", "guarantees",
                       "reduced", "reduced_why", "assumed", "server_env"):
                continue
            assert cell["config"][key] == value, key
        assert set(src["guarantees"]) <= set(cell["config"]["guarantees"])
    assert cell["config"]["lost_shards"] == LOST
    assert set(cell["config"]["assumed"]) >= {"schedule", "holders"}
    # what the cut does to the cell is said where the cut is
    assert "per volume" in cell["config"]["reduced_why"]
    got = {m["name"] for kind in ("end_to_end", "per_layer")
           for m in manifest.metrics_of(MAN, CELL, kind)}
    # `rebuild_MBps` is read and NOT listed: three of the builder's four
    # sets of six at 12 volumes spread by more than half its bound, two
    # runs of one set in the process's second mode (PERF.md, Findings,
    # PR 32; ROADMAP A13), and with it goes `reconstruct_kernel_roofline`,
    # which moves it; `req_p95_ms` is not listed (the sibling cell's
    # finding), nor `rebuild_unspanned_share` (it would read the clients'
    # seconds); the five `rebuild_*_share` stay the `rebuild` cell's
    # alone: their entries are pinned by tests/bench (PERF.md, section 7)
    assert cell["traffic"]["jobs"]["metric"] == "rebuild_MBps"    # `seen`
    assert got == {
        "req_per_s", "setup_s", *WANT, "req_device_idle_share",
        "req_compiles_in_window", "write_p99_ms", "longest_stall_ms",
        "client_cpu_share", "server_cpu_us_per_req",
        "load_req_ms_beside_job", "load_req_ms_alone",
        "load_req_beside_share"}
    for name in WANT:
        assert "`moves` is nominal" in manifest.reader(
            MAN, name).__globals__["__doc__"]
