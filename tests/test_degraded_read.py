"""The degraded read's third rung (seaweedfs_tpu/ec/degraded.py) and the
device coder's read path (ops/coder_pallas.py READ_WIDTHS), against the
plain reference — `NumpyCoder` — and, above it, the bytes themselves: a
degraded read returns what a healthy read of the same needle returns.

All on the CPU platform, the Pallas coder in interpret mode, in ONE file
(under `--dist loadfile` one worker runs it top to bottom, so the count
of compiled programs is this file's own).
"""

import itertools
import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.cluster import rpc
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ec import degraded, to_ext
from seaweedfs_tpu.ec.degraded import Lost, groups_of
from seaweedfs_tpu.ops import coder_pallas
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.ops.coder_pallas import (READ_PROGRAMS, PallasCoder,
                                            apply_bitmatrix_pallas)
from seaweedfs_tpu.ops.erasure import READ_WIDTHS, read_width
from seaweedfs_tpu.stats import roofline

MIB = 1 << 20
K, R = 10, 4


@pytest.fixture(scope="module")
def oracle():
    return NumpyCoder(K, R)


@pytest.fixture(scope="module")
def coder():
    pc = PallasCoder(K, R)
    pc.warm_reads()
    return pc


def _shards(oracle, n: int, seed: int = 0) -> np.ndarray:
    data = np.random.default_rng([seed, n]).integers(
        0, 256, (K, n), dtype=np.uint8)
    return oracle.encode_all(data)


# -- the widths ---------------------------------------------------------------

def test_the_width_list():
    assert READ_WIDTHS == tuple(sorted(READ_WIDTHS))
    assert READ_WIDTHS[0] == coder_pallas.BLOCK_N and READ_WIDTHS[-1] == MIB
    assert all(w % coder_pallas.BLOCK_N == 0 for w in READ_WIDTHS)
    assert read_width(1) == READ_WIDTHS[0]
    for w in READ_WIDTHS:
        assert read_width(w - 1) == read_width(w) == w
    assert read_width(READ_WIDTHS[0] + 1) == READ_WIDTHS[1]
    assert read_width(MIB + 1) == MIB       # the caller goes in pieces


EDGES = sorted({1, MIB, MIB + 1, 3 * MIB + 17}
               | {w + d for w in READ_WIDTHS for d in (-1, 0, 1)})


@pytest.mark.parametrize("n", EDGES)
def test_every_width_and_its_edges_against_the_reference(oracle, coder, n):
    """`reconstruct` at 1 byte, W - 1, W, W + 1 for every width, 1 MiB
    and past it (pieces): the lost rows are the reference's and the
    bytes that were there."""
    full = _shards(oracle, n)
    lost = (0, 3, 7, 12)
    have = {s: full[s] for s in range(K + R) if s not in lost}
    got = coder.reconstruct(have, wanted=list(lost))
    want = oracle.reconstruct(have, wanted=list(lost))
    for s in lost:
        assert got[s].shape == (n,)
        assert np.array_equal(got[s], want[s])
        assert np.array_equal(got[s], full[s])


@pytest.mark.parametrize("gone", [1, 2, 3, 4])
def test_every_solvable_loss_of_data_and_parity(oracle, coder, gone):
    """Every set of one to four shards among the fourteen: RS(10,4)
    solves them all, and each through the one program of its width
    (the wanted rows padded to four, the matrix kept)."""
    n = 2 * coder_pallas.BLOCK_N + 5          # the 16 KiB program
    full = _shards(oracle, n, seed=gone)
    programs = apply_bitmatrix_pallas._cache_size()
    for lost in itertools.combinations(range(K + R), gone):
        have = {s: full[s] for s in range(K + R) if s not in lost}
        got = coder.reconstruct(have, wanted=list(lost))
        for s in lost:
            assert np.array_equal(got[s], full[s]), lost
    assert apply_bitmatrix_pallas._cache_size() == programs
    # the kept matrices are bounded
    assert len(coder._mats) <= coder._MATS_KEPT


def test_a_loss_the_code_cannot_solve_raises(oracle, coder):
    full = _shards(oracle, 100)
    have = {s: full[s] for s in range(5, K + R)}        # nine survive
    with pytest.raises(ValueError):
        coder.reconstruct(have, wanted=[0])
    with pytest.raises(ValueError, match="out of range"):
        coder.reconstruct({s: full[s] for s in range(1, 11)}, wanted=[14])


def test_the_padded_call_is_one_transfer_one_program(oracle, coder):
    """`reconstruct_padded` on ONE pooled host array whose bytes past
    the interval are garbage: rows 0..m-1 are the wanted shards, the
    rest zeros; more wanted rows than the call gives is refused."""
    size, width = 5000, read_width(5000)
    full = _shards(oracle, size)
    present = tuple(range(4, 14))
    buf = np.full((K, width), 0xA5, np.uint8)
    for j, s in enumerate(present):
        buf[j, :size] = full[s]
    programs = apply_bitmatrix_pallas._cache_size()
    out = np.asarray(coder.reconstruct_padded(present, buf, (2, 0)))
    assert out.shape == (coder.read_rows, width)
    assert np.array_equal(out[0, :size], full[2])
    assert np.array_equal(out[1, :size], full[0])
    assert not out[2:].any()
    assert apply_bitmatrix_pallas._cache_size() == programs
    with pytest.raises(ValueError, match="rows wanted"):
        coder.reconstruct_padded(present, buf, (0, 1, 2, 3, 4))


def test_a_decode_matrix_is_built_once_a_pattern(oracle, coder,
                                                 monkeypatch):
    full = _shards(oracle, 64)
    have = {s: full[s] for s in range(1, 11)}
    coder.reconstruct(have, wanted=[0])
    calls = []
    real = coder.codec.decode_bitmatrix
    monkeypatch.setattr(coder.codec, "decode_bitmatrix",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    for _ in range(3):
        coder.reconstruct(have, wanted=[0])
    assert calls == []


# -- which intervals share a launch -----------------------------------------------

def test_intervals_of_one_stripe_row_share_a_launch():
    row = (False, 7)

    def lost(sid, off, size, row=()):
        return Lost(sid, off, size, np.empty(size, np.uint8), row)
    whole = [lost(s, 7 * MIB, MIB, row) for s in range(4)]
    assert groups_of(whole) == [[0, 1, 2, 3]]
    # a needle's tail on one block and its next block's head: one row,
    # a whole block apart: two narrow launches beat one wide one
    apart = [lost(2, 8 * MIB - 4096, 4096, row), lost(3, 7 * MIB, 4096, row)]
    assert groups_of(apart) == [[0], [1]]
    # a 4 MiB needle from the middle of a block: partial, whole, whole
    mixed = [lost(0, 7 * MIB + 300000, MIB - 300000, row),
             lost(1, 7 * MIB, MIB, row), lost(2, 7 * MIB, MIB, row)]
    assert groups_of(mixed) == [[0, 1, 2]]
    # two rows never share
    two = [lost(3, 7 * MIB, MIB, row), lost(0, 8 * MIB, MIB, (False, 8))]
    assert groups_of(two) == [[0], [1]]
    # no row said (the scrub's repair): alone
    assert groups_of([lost(0, 0, 10), lost(1, 0, 10)]) == [[0], [1]]


# -- through the volume server ----------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An in-process master and volume server whose coder is the Pallas
    one (interpret mode): needles of every kind of size uploaded, read
    back healthy from the sealed volumes, then data shards 0-3 removed.
    `needles` is {fid: (bytes written, bytes the healthy read gave)}."""
    mp = pytest.MonkeyPatch()
    mp.setenv("SEAWEEDFS_TPU_CODER", "pallas")
    tmp = str(tmp_path_factory.mktemp("degraded"))
    master = MasterServer(volume_size_limit_mb=64, meta_dir=tmp,
                          pulse_seconds=60)
    master.start()
    os.makedirs(os.path.join(tmp, "v"))
    vs = VolumeServer(master.url(), [os.path.join(tmp, "v")],
                      pulse_seconds=60, max_volume_counts=[30])
    vs.start()
    try:
        rng = np.random.default_rng(36)
        written = {}
        rpc.call(f"{master.url()}/vol/grow?count=1&collection=deg", "POST")
        # one byte; inside one block; a block's worth; over several
        # blocks; all in ONE volume, 45 MiB, so that needles lie over
        # the ends of its 10 MiB stripe rows
        for size in (1, 10, 5000, 300000, MIB, 4 * MIB + 17, 2500000,
                     3 * MIB, 4 * MIB, 4 * MIB, 4 * MIB, 3 * MIB + 1,
                     70000, 4 * MIB, 4 * MIB, 4 * MIB):
            data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            a = rpc.call(f"{master.url()}/dir/assign?collection=deg")
            rpc.call(f"http://{a['url']}/{a['fid']}", "POST", data)
            written[a["fid"]] = data
        url = vs.url()
        vids = sorted({int(f.split(",")[0]) for f in written})
        for vid in vids:
            for path in ("ec/generate", "ec/mount", "delete_volume"):
                rpc.call_json(f"http://{url}/admin/{path}", "POST",
                              {"volume": vid})
        healthy = {f: bytes(rpc.call(f"http://{url}/{f}"))
                   for f in written}
        for vid in vids:
            rpc.call_json(f"http://{url}/admin/ec/delete_shards", "POST",
                          {"volume": vid, "shards": [0, 1, 2, 3]})
        yield {"vs": vs, "url": url, "vids": vids, "master": master,
               "needles": {f: (written[f], healthy[f]) for f in written}}
    finally:
        vs.stop()
        master.stop()
        mp.undo()


def _sealed_alone(served, collection: str, lost: list[int],
                  codec: str | None = None, size: int = MIB + 4321):
    """(fid, bytes, vid) of one needle in a sealed volume of its own
    that lost `lost`."""
    m, url = served["master"].url(), served["url"]
    rpc.call(f"{m}/vol/grow?count=1&collection={collection}", "POST")
    a = rpc.call(f"{m}/dir/assign?collection={collection}")
    data = np.random.default_rng(len(collection)).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    rpc.call(f"http://{a['url']}/{a['fid']}", "POST", data)
    vid = int(a["fid"].split(",")[0])
    rpc.call_json(f"http://{url}/admin/ec/generate", "POST",
                  dict({"volume": vid}, **({"codec": codec} if codec else {})))
    for path in ("ec/mount", "delete_volume"):
        rpc.call_json(f"http://{url}/admin/{path}", "POST", {"volume": vid})
    assert _get(served, a["fid"]) == data
    rpc.call_json(f"http://{url}/admin/ec/delete_shards", "POST",
                  {"volume": vid, "shards": lost})
    return a["fid"], data, vid


def _rows() -> dict:
    return {r["kernel"]: r for r in roofline.LEDGER.stage_table()}


def _get(served, fid) -> bytes:
    return bytes(rpc.call(f"http://{served['url']}/{fid}"))


def test_a_degraded_read_returns_what_a_healthy_read_returned(served):
    """Every needle, from one byte to one that crosses stripe rows, with
    four data shards gone: the bytes written and the bytes the healthy
    read gave; the request rows say which GETs reached the rung."""
    before = _rows()
    for fid, (wrote, healthy) in served["needles"].items():
        assert healthy == wrote
        assert _get(served, fid) == wrote, (fid, len(wrote))
    after = _rows()

    def grew(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]
    assert grew("read.degraded") + grew("read.healthy") == \
        len(served["needles"])
    assert grew("read.degraded") >= 1
    # one gather, one dispatch and one drain a launch; lost intervals
    # of one stripe row share launches
    assert grew("read.gather") == grew("read.dispatch") == \
        grew("read.drain") >= grew("read.degraded")
    assert grew("read.interval") > grew("read.dispatch")


def test_a_needle_that_crosses_two_stripe_rows(served):
    """Some needle of the set lies over the end of a 10 MiB stripe row:
    it takes a launch at least for each row in which it has intervals
    on a lost shard, never one for two rows."""
    vs = served["vs"]
    crossing = []
    for fid, (wrote, _h) in served["needles"].items():
        vid, rest = fid.split(",")
        ev = vs.ec_volumes[int(vid)]
        key = int(rest[:-8], 16)
        _o, _s, intervals = ev.locate_needle(key)
        rows = {iv.block_index // K for iv in intervals}
        lost_rows = {iv.block_index // K for iv in intervals
                     if iv.block_index % K < 4}
        if len(rows) > 1:
            crossing.append((fid, wrote, len(lost_rows)))
    assert crossing, "no needle of the set crosses a stripe row"
    assert any(n for _f, _w, n in crossing)
    for fid, wrote, lost_rows in crossing:
        before = _rows()["read.dispatch"]["count"]
        assert _get(served, fid) == wrote
        assert _rows()["read.dispatch"]["count"] - before >= lost_rows


def test_intervals_at_a_blocks_first_and_last_byte(served, oracle):
    """The rung itself, on intervals a needle's layout rarely gives:
    one byte at a block's first and last offset, a whole block, and a
    block and a byte (pieces): the lost shard's own bytes, by the
    reference from the ten survivors."""
    vs = served["vs"]
    ev = max((vs.ec_volumes[v] for v in served["vids"]),
             key=lambda ev: ev.shard_size())
    size = ev.shard_size()
    assert size > MIB
    survivors = {}
    for sid in range(4, 14):
        with open(ev.base_file_name + to_ext(sid), "rb") as f:
            survivors[sid] = np.frombuffer(f.read(), dtype=np.uint8)
    for off, n in ((0, 1), (MIB - 1, 1), (MIB, 1), (0, MIB), (MIB - 1, 2),
                   (size - 1, 1), (0, MIB + 1), (size - MIB - 1, MIB + 1)):
        want = oracle.reconstruct(
            {s: row[off:off + n] for s, row in survivors.items()},
            wanted=[2])[2].tobytes()
        assert vs.degraded.interval(ev, 2, off, n) == want, (off, n)


def test_a_planned_read_that_fails_widens(served, monkeypatch):
    """A volume without shard 0, and shard 5 reads short: the plan's
    ten survivors are nine, the ladder asks the others (11, 12, 13 were
    not planned) and the answer is the same."""
    vs = served["vs"]
    fid, wrote, vid = _sealed_alone(served, "widen", [0])
    ev = vs.ec_volumes[vid]
    monkeypatch.setattr(ev.shards[5], "read_into", lambda buf, off: 0)
    monkeypatch.setattr(ev.shards[5], "read_at", lambda off, size: b"")
    before = _rows()["read.gather"]
    assert _get(served, fid) == wrote
    after = _rows()["read.gather"]
    launches = after["count"] - before["count"]
    assert launches >= 1
    # ten rows a launch all the same: nine of the plan and one more
    assert after["bytes"] - before["bytes"] > 0


def test_too_few_survivors_is_an_error_not_an_answer(served, monkeypatch):
    vs = served["vs"]
    fid, _ = next((f, v) for f, v in served["needles"].items()
                  if len(v[0]) == MIB)
    vid = int(fid.split(",")[0])
    ev = vs.ec_volumes[vid]
    vs._ec_shard_locations(vid)
    monkeypatch.setattr(ev.shards[5], "read_into", lambda buf, off: 0)
    monkeypatch.setattr(ev.shards[5], "read_at", lambda off, size: b"")
    with pytest.raises(degraded.Unrecoverable, match="reachable"):
        vs.degraded.interval(ev, 4, 0, 100)       # 0-3 gone, 4, 5 too
    assert vid not in vs._ec_loc_cache


def test_sixteen_readers_at_once_and_nothing_compiled_per_width(
        served, coder):
    """Sixteen threads read every needle, each in its own order: the
    same answers, and afterwards the read path has as many programs for
    RS's ten survivors as the list has widths — none for an interval's
    width, a loss pattern or a thread."""
    programs = apply_bitmatrix_pallas._cache_size()
    needles = list(served["needles"].items())
    wrong, errors = [], []

    def reader(i: int) -> None:
        try:
            order = np.random.default_rng(i).permutation(len(needles))
            for j in order:
                fid, (wrote, _h) = needles[j]
                if _get(served, fid) != wrote:
                    wrong.append(fid)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
    assert not errors and not wrong
    assert apply_bitmatrix_pallas._cache_size() == programs
    mine = [k for k in READ_PROGRAMS._ready
            if k[:2] == (K, R) and k[3:] == (coder.block_n, coder.mm,
                                             coder.interpret)]
    assert sorted(k[2] for k in mine) == list(READ_WIDTHS)


def test_a_get_racing_the_background_compile(served, monkeypatch):
    """The warm-up starts with nothing ready and GETs arrive at once:
    every answer is right, and each program was made once, by whoever
    came first."""
    fresh = coder_pallas._Programs()
    made = []
    real = fresh.ensure

    def counting(key, make, *args):
        def once():
            made.append(key)
            make(*args)
        return real(key, once)
    monkeypatch.setattr(fresh, "ensure", counting)
    monkeypatch.setattr(coder_pallas, "READ_PROGRAMS", fresh)
    th = degraded.warm_in_background()
    assert th is not None and th.name == "ec-read-warm"
    needles = list(served["needles"].items())
    wrong = []

    def reader() -> None:
        for fid, (wrote, _h) in needles:
            if _get(served, fid) != wrote:
                wrong.append(fid)
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for r in readers:
        r.start()
    for r in readers + [th]:
        r.join(300)
    assert not wrong
    assert len(made) == len(set(made)) == fresh.count() >= len(READ_WIDTHS)


def test_a_shape_waits_for_its_own_program_and_no_other():
    progs = coder_pallas._Programs()
    slow_started, release = threading.Event(), threading.Event()
    made = []

    def slow():
        slow_started.set()
        release.wait(30)
        made.append("slow")
    th = threading.Thread(target=progs.ensure, args=("slow", slow))
    th.start()
    assert slow_started.wait(30)
    t0 = time.monotonic()
    progs.ensure("quick", lambda: made.append("quick"))   # does not wait
    assert time.monotonic() - t0 < 5 and made == ["quick"]
    waiter = threading.Thread(target=progs.ensure,
                              args=("slow", lambda: made.append("twice")))
    waiter.start()
    release.set()
    th.join(30)
    waiter.join(30)
    assert made == ["quick", "slow"] and progs.count() == 2


def test_a_host_coder_warms_nothing(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_CODER", "numpy")
    assert degraded.warm_in_background() is None


def test_a_role_says_once_which_way_its_shard_reads_go(monkeypatch, capsys):
    """`command/servers.py` `_warm_reads`, as `server` and `volume`
    call it once they serve: the line names `read_many`'s way, and a
    host coder starts no compile thread."""
    from seaweedfs_tpu.command import servers
    from seaweedfs_tpu.ec.volume import read_many_path
    monkeypatch.setenv("SEAWEEDFS_TPU_CODER", "numpy")
    before = {th.name for th in threading.enumerate()}
    servers._warm_reads("server")
    assert f"server ec reads: {read_many_path()}" in capsys.readouterr().err
    assert {th.name for th in threading.enumerate()} == before


# -- LRC's local plan, and the scrub's repair -----------------------------------------

def test_lrc_reads_its_group_of_five(served):
    """LRC(12,2,2), data shard 0 gone: the rung gathers the five
    siblings of its locality group, not twelve survivors, and the
    needle reads back."""
    fid, data, vid = _sealed_alone(served, "lrc", [0], codec="lrc",
                                   size=200000)
    assert served["vs"].ec_volumes[vid].codec.name == "lrc"
    before = {(r["kernel"], r["codec"]) for r in
              roofline.LEDGER.stage_table()}
    assert ("read.gather", "lrc") not in before
    assert _get(served, fid) == data
    after = {(r["kernel"], r["codec"]): r
             for r in roofline.LEDGER.stage_table()}
    gather = after[("read.gather", "lrc")]
    interval = after[("read.interval", "lrc")]
    assert gather["bytes"] == 5 * interval["bytes"] > 0


def test_the_scrubs_repair_goes_through_the_same_rung(served):
    """A flipped byte in a surviving shard's block of a volume without
    shard 0: the scrub finds it by the `.ecc` CRC and repairs it
    through `degraded.interval` (one more launch), and the shard file
    is what it was."""
    vs, url = served["vs"], served["url"]
    fid, data, vid = _sealed_alone(served, "scrub", [0])
    ev = vs.ec_volumes[vid]
    path = ev.base_file_name + to_ext(6)
    with open(path, "rb") as f:
        sound = f.read()
    with open(path, "r+b") as f:
        f.seek(1234)
        f.write(bytes([sound[1234] ^ 0xFF]))
    launches = _rows()["read.dispatch"]["count"]
    rpc.call_json(f"http://{url}/admin/scrub", "POST",
                  {"volume": vid, "repair": True})
    with open(path, "rb") as f:
        assert f.read() == sound
    assert _rows()["read.dispatch"]["count"] > launches
    assert _get(served, fid) == data
