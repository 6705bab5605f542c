"""Sealing while serving (PERF.md, configuration `live-ec-maintenance`):
one process answers needle requests and runs EC admin jobs.

- a served seal of a 40 MiB volume, through the admin shell, while client
  threads write 1 KB needles into other volumes of the same server: every
  shard, `.ecc` and `.ecx` is the plain reference's (benchmark/ecref.py)
  byte for byte, every acknowledged write reads back, no request failed,
  and no write landed in the volume being sealed;
- the two request rows of stats/roofline.py: their counts sum to the
  needle requests the server answered, which row a request lands in, the
  job mark's way down when a job raises, and the kill switch;
- the five `load_*` readers of benchmark/metrics/ on facts made by hand.

All in process on the CPU platform (the Pallas coder in interpret mode
where a coder runs), in ONE file: under `--dist loadfile` one worker runs
them one after another.  Every wait is for a state, with a deadline.
"""

import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import ecjobs, ecref, manifest  # noqa: E402
from benchmark.data import (Http, payload_block,  # noqa: E402
                            request_payload)
from seaweedfs_tpu.cluster import rpc  # noqa: E402
from seaweedfs_tpu.cluster.master import MasterServer  # noqa: E402
from seaweedfs_tpu.cluster.volume_server import VolumeServer  # noqa: E402
from seaweedfs_tpu.shell import CommandEnv, run_command  # noqa: E402
from seaweedfs_tpu.stats import roofline  # noqa: E402

MIB = 1 << 20
DEADLINE = 120.0
BESIDE, ALONE = "req.beside_job", "req.alone"


@pytest.fixture(autouse=True)
def _clean_ledger():
    roofline.LEDGER.reset()
    yield
    roofline.set_armed(True)
    roofline.LEDGER.reset()


@pytest.fixture
def cluster(tmp_path):
    """An in-process master and volume server; what `benchmark/ecjobs.py`
    wants to know of a server, under the names it uses."""
    work = str(tmp_path)
    master = MasterServer(volume_size_limit_mb=64, meta_dir=work,
                          pulse_seconds=60)
    master.start()
    data_dir = os.path.join(work, "data")
    os.makedirs(data_dir)
    vs = VolumeServer(master.url(), [data_dir], pulse_seconds=60)
    vs.start()
    try:
        yield SimpleNamespace(master=master.url(), volume=vs.server.url(),
                              vport=vs.server.port, data_dir=data_dir,
                              work=work, vs=vs)
    finally:
        vs.stop()
        master.stop()


def wait_for(what: str, ready, deadline: float = DEADLINE):
    """The value of `ready()` once it is true."""
    end = time.monotonic() + deadline
    while time.monotonic() < end:
        got = ready()
        if got:
            return got
        time.sleep(0.005)
    raise AssertionError(f"not within {deadline:.0f} s: {what}")


def request_rows() -> dict:
    rows = {r["kernel"]: r for r in roofline.LEDGER.stage_table()}
    return {name: rows.get(name, {"count": 0, "seconds": 0.0, "bytes": 0})
            for name in (BESIDE, ALONE)}


def rows_at(total: int) -> dict:
    """The request rows once they hold `total` requests (a row is added
    after the response is written: the client may be ahead by a few
    microseconds)."""
    def ready():
        rows = request_rows()
        return rows if rows[BESIDE]["count"] + rows[ALONE]["count"] \
            >= total else None
    return wait_for(f"{total} requests in the rows", ready, 10.0)


# -- a served seal under concurrent writes ----------------------------------

class Writers:
    """Closed-loop clients as the benchmark's generator runs them:
    assign + upload of 1 KB, each on a thread with its own connections."""

    def __init__(self, master: str, seed: int, clients: int):
        self.block = payload_block(seed)
        self.acked: list[list] = [[] for _ in range(clients)]
        self.failed: list = []
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run, args=(master, c),
                             daemon=True) for c in range(clients)]
        for t in self._threads:
            t.start()

    def _run(self, master: str, client: int) -> None:
        http = Http(master)
        try:
            n = 0
            while not self._stop.is_set():
                ident = (client << 32) | n
                try:
                    fid, url = http.write(
                        "bench", request_payload(self.block, ident, 1024))
                    self.acked[client].append((ident, fid, url))
                except Exception as e:  # noqa: BLE001 - asserted empty
                    self.failed.append(e)
                n += 1
        finally:
            http.close()

    def done(self) -> list[int]:
        return [len(a) for a in self.acked]

    def each_wrote(self, more: int):
        """Every client has `more` acknowledged writes beyond now."""
        start = self.done()
        wait_for(f"{more} more writes of every client",
                 lambda: all(n >= s + more
                             for n, s in zip(self.done(), start)))

    def stop(self) -> list:
        self._stop.set()
        for t in self._threads:
            t.join(DEADLINE)
            assert not t.is_alive()
        return [w for a in self.acked for w in a]


def sorted_index(idx_path: str) -> bytes:
    """`.ecx` as the reference makes it: the `.idx` entries (16 bytes:
    key, offset, size, big-endian) in key order."""
    raw = np.fromfile(idx_path, np.uint8).reshape(-1, 16)
    keys = raw[:, :8].copy().view(">u8").ravel()
    assert len(set(keys.tolist())) == len(keys)
    return raw[np.argsort(keys, kind="stable")].tobytes()


def test_served_seal_under_concurrent_writes_is_the_reference(
        cluster, monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_CODER", "pallas")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", "1")
    seed = 2**31 + 27
    tpl = ecjobs.fill_volume(cluster, seed, 21, "pool", 40 * MIB,
                             (4096, MIB))
    idx_before = sorted_index(tpl.base + ".idx")
    rpc.call(f"{cluster.master}/vol/grow?count=3&collection=bench", "POST")
    roofline.LEDGER.reset()
    writers = Writers(cluster.master, seed, clients=4)
    env = CommandEnv(cluster.master)
    try:
        writers.each_wrote(5)                 # the load is on
        run_command(env, "lock")
        out = run_command(env, f"ec.encode -volumeId {tpl.vid}")
        assert f"volume {tpl.vid} -> ec shards" in out
        writers.each_wrote(5)                 # and outlasts the seal
    finally:
        acked = writers.stop()
        env.close()

    # the sealed volume against the plain reference, byte for byte
    assert ecjobs.files_missing(tpl.base, tpl) == 0
    assert not os.path.exists(tpl.base + ".dat")
    assert os.path.getsize(tpl.kept_dat) == tpl.dat_bytes
    crcs = ecref.load_ecc(tpl.base)
    for row in range(ecref.shard_size(tpl.dat_bytes) // ecref.BLOCK):
        data = ecref.dat_row(tpl.kept_dat, row)
        want = np.concatenate([data, ecref.encode(data)])
        for sid in range(ecref.TOTAL_SHARDS):
            got = ecref.read_block(tpl.base + ecref.ext(sid), row)
            assert np.array_equal(got, want[sid]), (row, sid)
            assert crcs[sid][row] == ecref.crc32c(want[sid]), (row, sid)
    assert sorted(crcs) == list(range(ecref.TOTAL_SHARDS))
    with open(tpl.base + ".ecx", "rb") as f:
        assert f.read() == idx_before
    assert ecjobs.compare_needles(cluster, tpl, seed, tpl.vid, 4)[
        "needles_differ"] == 0

    # the clients: nothing failed, nothing landed in the sealed volume,
    # every acknowledged write reads back
    assert not writers.failed
    assert acked and all(n >= 10 for n in writers.done())
    assert tpl.vid not in {int(fid.split(",")[0]) for _i, fid, _u in acked}
    http = Http(cluster.master)
    try:
        for ident, fid, url in acked:
            assert http.read(url, fid) == request_payload(
                writers.block, ident, 1024), fid
    finally:
        http.close()

    # and the rows saw both: uploads beside the seal and alone, then the
    # reads above (5 of the sealed volume's needles, every write's), alone
    rows = rows_at(2 * len(acked) + 5)
    assert rows[BESIDE]["count"] + rows[ALONE]["count"] == \
        2 * len(acked) + 5
    assert rows[BESIDE]["count"] >= 1 and rows[ALONE]["count"] >= 1
    assert rows[BESIDE]["bytes"] + rows[ALONE]["bytes"] == \
        1024 * len(acked)
    assert roofline.jobs_running() == 0


# -- the request rows -----------------------------------------------------------

def put(cluster, payload: bytes = b"x" * 100) -> tuple[str, str]:
    a = rpc.call(f"{cluster.master}/dir/assign")
    url = f"http://{a['url']}/{a['fid']}"
    rpc.call(url, "POST", payload)
    return url, a["fid"]


def test_request_rows_count_every_needle_request_and_nothing_else(cluster):
    urls = [put(cluster, b"y" * (100 + i))[0] for i in range(6)]
    for url in urls[:4]:
        assert len(rpc.call(url)) >= 100
    rpc.call(urls[0], "DELETE")
    with pytest.raises(rpc.RpcError):
        rpc.call(urls[0])                      # answered: 404
    # admin, debug and heartbeat routes are not needle requests
    rpc.call(f"{cluster.volume}/debug/device")
    rpc.call(f"{cluster.volume}/admin/status")
    cluster.vs._send_heartbeat(full=True)
    rows = rows_at(12)
    assert rows[ALONE]["count"] == 12 and rows[BESIDE]["count"] == 0
    assert rows[ALONE]["bytes"] == sum(100 + i for i in range(6))
    assert rows[ALONE]["seconds"] > 0
    doc = rpc.call(f"{cluster.volume}/debug/device")
    served = {r["kernel"]: r for r in doc["kernels"]}
    assert served[ALONE]["count"] == 12 and served[ALONE]["codec"] == ""
    assert BESIDE not in served


def test_a_request_is_beside_a_job_that_runs_at_either_end(cluster):
    """A read held inside its handler while a job starts, and one that
    starts inside a job and ends after it: both beside.  The handler is
    held where the server routes to it."""
    url, _fid = put(cluster)
    rows_at(1)
    entered, go = threading.Event(), threading.Event()
    routes = cluster.vs.server.prefix_routes
    at = next(i for i, r in enumerate(routes) if r[0] == "GET")
    method, prefix, handler, stream = routes[at]

    def held(path, query, body):
        entered.set()
        assert go.wait(DEADLINE)
        return handler(path, query, body)

    routes[at] = (method, prefix, held, stream)

    def one_read(job_first: bool, total: int) -> None:
        entered.clear()
        go.clear()
        got: list = []
        job = roofline.ec_job()
        if job_first:
            job.__enter__()
        t = threading.Thread(target=lambda: got.append(rpc.call(url)))
        t.start()
        assert entered.wait(DEADLINE)
        if job_first:
            job.__exit__(None, None, None)
        else:
            job.__enter__()
        go.set()
        t.join(DEADLINE)
        assert not t.is_alive()
        if not job_first:
            # the server books the row after the response is written:
            # the job lasts until it has
            rows_at(total)
            job.__exit__(None, None, None)
        assert got == [b"x" * 100]

    one_read(job_first=False, total=2)   # starts alone, ends inside the job
    rows = rows_at(2)
    assert (rows[BESIDE]["count"], rows[ALONE]["count"]) == (1, 1)
    one_read(job_first=True, total=3)    # starts inside, ends after it
    rows = rows_at(3)
    assert (rows[BESIDE]["count"], rows[ALONE]["count"]) == (2, 1)
    routes[at] = (method, prefix, handler, stream)
    rpc.call(url)
    rows = rows_at(4)
    assert (rows[BESIDE]["count"], rows[ALONE]["count"]) == (2, 2)
    assert roofline.jobs_running() == 0


def test_a_job_that_raises_takes_the_mark_down(cluster):
    for path in ("ec/generate", "ec/rebuild"):
        with pytest.raises(rpc.RpcError):
            rpc.call_json(f"{cluster.volume}/admin/{path}", "POST",
                          {"volume": 999})
        assert roofline.jobs_running() == 0
    with pytest.raises(RuntimeError, match="device lost"):
        with roofline.ec_job():
            assert roofline.jobs_running() == 1
            raise RuntimeError("device lost")
    assert roofline.jobs_running() == 0
    put(cluster)
    rows = rows_at(1)
    assert (rows[BESIDE]["count"], rows[ALONE]["count"]) == (0, 1)


def test_the_mark_and_the_rows_lose_no_update_between_threads():
    """More threads than cores, the interpreter switching as often as it
    can: every job's mark comes down and every request is in a row."""
    threads, each = 4 * (os.cpu_count() or 4), 300
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work() -> None:
        for i in range(each):
            t0 = time.perf_counter()
            beside = roofline.jobs_running()
            if i % 3:
                roofline.note_request(t0, beside, 1)
            else:
                with roofline.ec_job():
                    roofline.note_request(t0, beside, 1)

    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(DEADLINE)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert roofline.jobs_running() == 0
    rows = request_rows()
    assert rows[BESIDE]["count"] + rows[ALONE]["count"] == threads * each
    assert rows[BESIDE]["bytes"] + rows[ALONE]["bytes"] == threads * each
    assert rows[BESIDE]["count"] >= threads * (each // 3)


def test_disarmed_the_request_rows_record_nothing(cluster):
    roofline.set_armed(False)
    url, _fid = put(cluster)
    with roofline.ec_job():
        assert rpc.call(url) == b"x" * 100
    roofline.set_armed(True)
    assert request_rows() == {
        name: {"count": 0, "seconds": 0.0, "bytes": 0}
        for name in (BESIDE, ALONE)}
    rpc.call(url)
    assert rows_at(1)[ALONE]["count"] == 1


# -- the readers ------------------------------------------------------------------

MAN = manifest.load()
ROWS = {"seal.stack_wait": (42, 0.25), "seal.stack": (39, 3.0),
        "seal.dispatch": (39, 1.5), "seal.write_data": (39, 1.0),
        "seal.drain": (39, 0.5), "seal.write_parity": (39, 0.65),
        "seal.finish": (6, 0.25), "seal.mount": (3, 0.5),
        "seal.delete_original": (3, 0.35),
        "encode_crc_kernel": (39, 1.25),
        BESIDE: (4000, 48.0), ALONE: (6000, 54.0)}
WANT = {"load_seal_share": 50.0,               # 5.0 s of 10, no seal.stack
        "load_seal_ms_per_chunk": 100.0,       # 3.9 s over 39 chunks
        "load_req_ms_beside_job": 12.0,
        "load_req_ms_alone": 9.0,
        "load_req_beside_share": 40.0}


def facts(rows: dict) -> dict:
    return {"window_s": 10.0, "trace": None,
            "requests": {"op": "write", "attempted": 8000, "failed": 0},
            "jobs": {"op": "ec.encode", "count": 3, "shard_bytes": 1,
                     "lost": 0},
            "coder_rows": {k: {"count": c, "seconds": s, "bytes": 1}
                           for k, (c, s) in rows.items()}}


@pytest.mark.parametrize("name,want", WANT.items())
def test_load_readers_on_facts_made_by_hand(name, want):
    read = manifest.reader(MAN, name)
    assert read(facts(ROWS)) == pytest.approx(want)
    # a program without the rows this metric reads (the parent: no
    # request rows; a commit before the stage clock: kernel rows only)
    mine = "req." if "_req_" in name else "seal."
    without = {k: v for k, v in ROWS.items() if not k.startswith(mine)}
    assert read(facts(without)) is None
    assert read(facts({})) is None
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    assert "seal-under-load" in entry["workloads"]
    assert entry["moves"] == ("req_per_s" if "_req_" in name
                              else "seal_MBps")
    assert entry["layer"] == ("rpc plane + volume engine"
                              if "_req_" in name else "EC file pipeline")


def test_load_req_beside_share_reads_zero_where_no_job_ran():
    read = manifest.reader(MAN, "load_req_beside_share")
    only_alone = {k: v for k, v in ROWS.items() if k != BESIDE}
    assert read(facts(only_alone)) == 0.0
    assert manifest.reader(MAN, "load_req_ms_beside_job")(
        facts(only_alone)) is None


def test_the_new_cell_is_entries_appended_and_files_added():
    """PR 27's entries come after everything PR 25 left, in this order
    and side by side (read by membership: later PRs retired entries
    ahead of them and appended others behind), and the cell reports
    what the issue lists for it."""
    assert [c["name"] for c in MAN["configs"]][2:3] == [
        "live-ec-maintenance"]
    assert [w["name"] for w in MAN["workloads"]][3:4] == ["seal-under-load"]
    names = [m["name"] for m in MAN["per_layer"]]
    first = names.index(next(iter(WANT)))
    assert names[first:first + len(WANT)] == list(WANT)
    assert first > names.index("seal_unspanned_share")
    cell = manifest.cell(MAN, "seal-under-load")
    assert cell["traffic"]["jobs"] == {
        "op": "ec.encode", "per_second": 0.3, "metric": "seal_MBps"}
    assert cell["traffic"]["requests"] == {"op": "write",
                                           "warm_seconds": 3}
    assert "ec" not in cell["traffic"]
    # both parents' values, unchanged
    for parents_cell in ("seal", "bench-write-1k"):
        src = manifest.cell(MAN, parents_cell)["config"]
        for key, value in src.items():
            # `server_env` is the parents' servers' allocator (PR 34),
            # which this configuration does not take over (PERF.md §7)
            if key in ("name", "source", "deployment", "guarantees",
                       "reduced", "reduced_why", "assumed", "lost_shards",
                       "server_env"):
                continue
            assert cell["config"][key] == value, key
        assert set(src["guarantees"]) <= set(cell["config"]["guarantees"])
    assert cell["config"]["lost_shards"] == []
    got = {m["name"] for kind in ("end_to_end", "per_layer")
           for m in manifest.metrics_of(MAN, "seal-under-load", kind)}
    # `req_p95_ms` is not listed: one of the builder's two sets of six
    # spread by more than half its bound (PERF.md, Findings, PR 27)
    assert got == {
        "seal_MBps", "req_per_s", "setup_s", *WANT,
        "encode_kernel_roofline", "req_device_idle_share",
        "req_compiles_in_window", "write_p99_ms", "longest_stall_ms",
        "client_cpu_share", "server_cpu_us_per_req"}
