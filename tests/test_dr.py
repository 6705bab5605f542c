"""Disaster recovery: the durable per-volume change log (`.rlog`),
cross-cluster active/passive mirroring, and verified failover.

Three layers, matching the replication plane's own structure:

- `.rlog` / `.rwm` unit tests — crash-safe append/recover semantics
  (torn tail, CRC-bad tail, rotten head, vacuum compaction, watermark
  monotonicity) on a bare tmpdir, no servers.
- A two-cluster `mirror` fixture (primary = single-node-raft master +
  volume server with `-replicate.peer`; standby = plain master +
  volume server) driving the real shipper: byte-identical convergence,
  tombstone propagation (a delete must never resurrect), duplicate
  delivery, WAN partition + heal, the master's lag SLO in
  /cluster/healthz, raft leader failover with records in flight,
  `volume.fsck -crc -json` convergence proof, the cluster.mirror.*
  shell verbs, and promcheck-gated metrics.
- Function-scoped chaos: restart both sides mid-backlog (shipping
  resumes exactly from the durable watermarks) and
  `cluster.mirror.cutover` under live client load with zero
  client-visible errors and zero acked-write loss.
"""

import json
import os
import threading
import time

import pytest

from seaweedfs_tpu import fault
from seaweedfs_tpu.cluster import resilience, rpc
from seaweedfs_tpu.cluster.client import WeedClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.core import types as t
from seaweedfs_tpu.replication import rlog as rl
from seaweedfs_tpu.replication.rlog import (LogRecord, RECORD_SIZE,
                                            ReplicationLog, Watermark)
from seaweedfs_tpu.shell import CommandEnv, run_command
from seaweedfs_tpu.stats.metrics import replication_resends_total
from seaweedfs_tpu.stats.promcheck import validate_exposition

pytestmark = pytest.mark.dr


@pytest.fixture(autouse=True)
def _clean():
    fault.disarm_all()
    resilience.reset_breakers()
    yield
    fault.disarm_all()
    resilience.reset_breakers()


def _wait(cond, timeout=20.0, msg="condition never held"):
    deadline = time.time() + timeout
    while not cond():
        if time.time() > deadline:
            raise TimeoutError(msg)
        time.sleep(0.05)


# -- change-log unit tests ---------------------------------------------------

def test_record_roundtrip_and_crc_gate():
    rec = LogRecord(7, rl.OP_WRITE, 0xDEADBEEF, 1234, 77, 999_000)
    buf = rec.to_bytes()
    assert len(buf) == RECORD_SIZE == 40
    assert LogRecord.from_bytes(buf) == rec
    # One flipped byte anywhere must fail the CRC gate.
    assert LogRecord.from_bytes(buf[:-1] + bytes([buf[-1] ^ 1])) is None
    assert LogRecord.from_bytes(bytes([buf[0] ^ 0x80]) + buf[1:]) is None
    # A short buffer is a torn tail, not an exception.
    assert LogRecord.from_bytes(buf[:RECORD_SIZE - 1]) is None


def test_append_read_reopen_resume(tmp_path):
    base = str(tmp_path / "7")
    log = ReplicationLog(base)
    for i in range(5):
        assert log.append(rl.OP_WRITE, 100 + i, 9, 64) == i + 1
    recs = log.read_from(1, 100)
    assert [r.seq for r in recs] == [1, 2, 3, 4, 5]
    assert [r.needle_id for r in recs] == [100, 101, 102, 103, 104]
    # Arithmetic seek: start mid-log, bounded batch.
    assert [r.seq for r in log.read_from(3, 2)] == [3, 4]
    log.close()
    log2 = ReplicationLog(base)
    assert (log2.first_seq, log2.last_seq) == (1, 5)
    assert log2.append(rl.OP_DELETE, 100, 0, 0) == 6
    log2.close()


def test_torn_partial_tail_truncated_on_open(tmp_path):
    base = str(tmp_path / "8")
    log = ReplicationLog(base)
    for i in range(3):
        log.append(rl.OP_WRITE, i, 0, 10)
    log.close()
    with open(base + ".rlog", "ab") as f:
        f.write(b"\xfe" * 17)  # crash mid-append: a partial record
    log2 = ReplicationLog(base)
    assert log2.last_seq == 3
    assert [r.seq for r in log2.read_from(1, 10)] == [1, 2, 3]
    assert os.path.getsize(base + ".rlog") == 3 * RECORD_SIZE
    log2.close()


def test_crc_bad_tail_stepped_back_over(tmp_path):
    base = str(tmp_path / "9")
    log = ReplicationLog(base)
    for i in range(3):
        log.append(rl.OP_WRITE, i, 0, 10)
    log.close()
    with open(base + ".rlog", "r+b") as f:  # rot inside the LAST record
        f.seek(2 * RECORD_SIZE + 5)
        f.write(b"\xff")
    log2 = ReplicationLog(base)
    assert log2.last_seq == 2, "CRC-bad tail record must be dropped"
    assert log2.append(rl.OP_WRITE, 9, 0, 10) == 3
    log2.close()


def test_rotten_head_resets_and_resumes_from_watermark(tmp_path):
    base = str(tmp_path / "10")
    log = ReplicationLog(base)
    for i in range(3):
        log.append(rl.OP_WRITE, i, 0, 10)
    log.set_acked(2)
    log.close()
    with open(base + ".rlog", "r+b") as f:  # head record rots
        f.seek(3)
        f.write(b"\xff")
    log2 = ReplicationLog(base)
    # Broken seq arithmetic -> full reset; the seq chain resumes from
    # the durable acked watermark, so already-acked seqs never recur.
    assert log2.first_seq == 0
    assert log2.last_seq == 2 == log2.acked_seq
    assert log2.append(rl.OP_WRITE, 9, 0, 10) == 3
    log2.close()


def test_missing_log_resumes_seq_from_watermark(tmp_path):
    base = str(tmp_path / "11")
    log = ReplicationLog(base)
    for i in range(3):
        log.append(rl.OP_WRITE, i, 0, 10)
    log.set_acked(3)
    log.close()
    os.remove(base + ".rlog")
    log2 = ReplicationLog(base)
    assert log2.last_seq == 3 and log2.pending() == 0
    assert log2.append(rl.OP_WRITE, 9, 0, 10) == 4
    log2.close()


def test_compact_drops_acked_prefix_keeps_seq_chain(tmp_path):
    base = str(tmp_path / "12")
    log = ReplicationLog(base)
    for i in range(5):
        log.append(rl.OP_WRITE, i, 0, 10)
    log.set_acked(3)
    assert log.compact() == 3
    recs = log.read_from(1, 100)  # clamps to first_seq
    assert [r.seq for r in recs] == [4, 5, 6]
    assert recs[-1].op == rl.OP_VACUUM
    assert (log.first_seq, log.last_seq) == (4, 6)
    assert log.pending() == 3
    log.close()
    # The compacted file alone still carries the chain.
    log2 = ReplicationLog(base)
    assert (log2.first_seq, log2.last_seq) == (4, 6)
    assert log2.append(rl.OP_WRITE, 9, 0, 10) == 7
    # Fully-acked log: compaction leaves just the vacuum record.
    log2.set_acked(7)
    log2.compact()
    recs = log2.read_from(1, 100)
    assert len(recs) == 1 and recs[0].op == rl.OP_VACUUM
    assert recs[0].seq == 8 == log2.last_seq
    log2.close()


def test_watermark_is_monotonic_and_durable(tmp_path):
    path = str(tmp_path / "13.rwm")
    wm = Watermark(path)
    wm.set(5)
    wm.set(3)  # regression is a no-op: acks never move backwards
    assert wm.value == 5
    assert Watermark(path).value == 5  # survives reopen
    wm.remove()
    assert Watermark(path).value == 0


# -- two-cluster mirror ------------------------------------------------------

@pytest.fixture(scope="module")
def mirror(tmp_path_factory):
    """Primary (single-node-raft master + shipper-bearing volume
    server) mirroring into a standby (plain master + volume server).
    The lag SLO is deliberately tight (50ms) so breach tests are
    fast; shipping at 50ms ticks keeps steady-state lag under it."""
    tmp = tmp_path_factory.mktemp("mirror")
    sb_master = MasterServer(volume_size_limit_mb=16,
                             meta_dir=str(tmp / "sbmeta"),
                             pulse_seconds=60)
    sb_master.start()
    (tmp / "sb").mkdir()
    sb_vs = VolumeServer(sb_master.url(), [str(tmp / "sb")],
                         max_volume_counts=[200], pulse_seconds=60)
    sb_vs.start()
    pport = rpc.free_port()
    pr_master = MasterServer(port=pport, volume_size_limit_mb=16,
                             meta_dir=str(tmp / "prmeta"),
                             pulse_seconds=60,
                             peers=[f"http://127.0.0.1:{pport}"],
                             replication_lag_slo=0.05)
    pr_master.start()
    _wait(pr_master.is_leader, 15, "single-node raft never elected")
    (tmp / "pr").mkdir()
    pr_vs = VolumeServer(pr_master.url(), [str(tmp / "pr")],
                         max_volume_counts=[200], pulse_seconds=60,
                         replicate_peer=sb_master.url(),
                         replicate_interval=0.05)
    pr_vs.start()
    yield pr_master, pr_vs, sb_master, sb_vs, tmp
    pr_vs.stop()
    pr_master.stop()
    sb_vs.stop()
    sb_master.stop()


_COL_N = [0]


def _put(mir, data, collection=None):
    """Journaled write on the primary: grow-if-new collection, enable
    the change log BEFORE the write lands (a write that precedes the
    log's creation has nothing to ship), raw POST.  Returns (vid, fid,
    collection)."""
    pr_master, pr_vs = mir[0], mir[1]
    if collection is None:
        _COL_N[0] += 1
        collection = f"drcol{_COL_N[0]}"
        rpc.call(f"{pr_master.url()}/vol/grow?count=1"
                 f"&collection={collection}", "POST")
    a = rpc.call(f"{pr_master.url()}/dir/assign?collection={collection}")
    vid = int(a["fid"].split(",")[0])
    v = pr_vs.store.find_volume(vid)
    if v.rlog is None:
        v.enable_rlog()
    rpc.call(f"http://{a['url']}/{a['fid']}", "POST", data)
    return vid, a["fid"], collection


def _rlog_status(vs, vid):
    doc = rpc.call(f"http://{vs.url()}/debug/replication")
    return (doc.get("rlog") or {}).get(str(vid))


def _wait_shipped(vs, vid, timeout=20.0):
    """Until the change log has nothing pending AND the shipper's lag
    view says so too.  The view is what the next heartbeat carries to
    the master's healthz, and the shipper brings it up to date only
    after it has moved the log's watermark and journaled the ack: a
    caller that watched the log alone could heartbeat the lag of before
    the ship — past a 50 ms SLO on a loaded machine."""
    def ok():
        st = _rlog_status(vs, vid)
        if not st or st["pending"] or not st["last_seq"]:
            return False
        seen = vs.shipper.lag_view()["volumes"].get(str(vid))
        return seen is not None and seen["lag_seq"] == 0 \
            and seen["acked_seq"] == st["acked_seq"]
    _wait(ok, timeout, f"volume {vid} never fully shipped: "
                       f"{_rlog_status(vs, vid)}")


def test_mirror_converges_byte_identical(mirror):
    pr_master, pr_vs, sb_master, _sb_vs, _tmp = mirror
    payloads = [f"mirror payload {i} ".encode() * 32 for i in range(3)]
    vid, fid0, col = _put(mirror, payloads[0])
    fids = [fid0]
    for p in payloads[1:]:
        fids.append(_put(mirror, p, collection=col)[1])
    _wait_shipped(pr_vs, vid)
    sbc = WeedClient(sb_master.url())
    for fid, p in zip(fids, payloads):
        assert sbc.download(fid) == p
    # The standby holds the volume under the same id + collection.
    st = _rlog_status(pr_vs, vid)
    assert st["acked_seq"] == st["last_seq"] >= len(payloads)


def test_tombstone_propagates_and_never_resurrects(mirror):
    _pm, pr_vs, sb_master, sb_vs, _tmp = mirror
    vid, fid, col = _put(mirror, b"doomed needle " * 16)
    _wait_shipped(pr_vs, vid)
    sbc = WeedClient(sb_master.url())
    assert sbc.download(fid)
    rpc.call(f"http://{pr_vs.url()}/{fid}", "DELETE")
    _wait_shipped(pr_vs, vid)
    with pytest.raises(rpc.RpcError) as ei:
        sbc.download(fid)
    assert ei.value.status == 404
    # Replay the WHOLE already-acked log at the standby: every record
    # is behind its applied watermark, so nothing applies and the
    # tombstone holds — a delete must never resurrect.
    v = pr_vs.store.find_volume(vid)
    recs = v.rlog.read_from(1, 1000)
    body = {"volume": vid, "collection": col, "version": v.version,
            "replication": "000", "ttl": "",
            "records": [{"seq": r.seq, "op": r.op,
                         "needle_id": r.needle_id, "cookie": r.cookie,
                         "size": r.size, "ts_ns": r.ts_ns,
                         "blob": None} for r in recs]}
    out = rpc.call_json(f"http://{sb_vs.url()}/admin/replication/apply",
                        "POST", body)
    assert out["applied"] == 0 and out["skipped"] == len(recs)
    with pytest.raises(rpc.RpcError):
        sbc.download(fid)


def test_journal_commit_points_and_quarantine_stays_local(mirror):
    """The volume journals at the needle commit points (write +
    delete carry the needle id/cookie), while scrub quarantine — local
    hygiene whose remote copy is healthy — must NOT journal: shipping
    a quarantine as a delete would destroy the standby's good copy."""
    _pm, pr_vs, _sbm, _sbv, _tmp = mirror
    vid, fid, col = _put(mirror, b"journaled write " * 16)
    v = pr_vs.store.find_volume(vid)
    _vid, key, cookie = t.parse_file_id(fid)
    recs = v.rlog.read_from(1, 100)
    assert any(r.op == rl.OP_WRITE and r.needle_id == key
               and r.cookie == cookie and r.size > 0 for r in recs)
    rpc.call(f"http://{pr_vs.url()}/{fid}", "DELETE")
    recs = v.rlog.read_from(1, 100)
    assert recs[-1].op == rl.OP_DELETE and recs[-1].needle_id == key
    # A second, live needle to quarantine.
    _vid2, fid2, _c = _put(mirror, b"healthy elsewhere " * 16,
                           collection=col)
    _wait_shipped(pr_vs, vid)
    last = v.rlog.last_seq
    _vid2, key2, _ck2 = t.parse_file_id(fid2)
    assert v.quarantine_needle(key2)
    assert v.rlog.last_seq == last, \
        "quarantine must not journal a cross-cluster tombstone"
    # Cleanup: drop the quarantined volume so /cluster/healthz stays
    # clean for the SLO test below.
    rpc.call_json(f"http://{pr_vs.url()}/admin/delete_volume", "POST",
                  {"volume": vid})
    pr_vs._send_heartbeat(full=True)


def test_duplicate_delivery_is_a_noop(mirror):
    _pm, pr_vs, sb_master, _sbv, _tmp = mirror
    before = replication_resends_total.value(reason="duplicate")
    fault.arm("wan.duplicate", "fail*1")
    payload = b"delivered twice, stored once " * 8
    vid, fid, _col = _put(mirror, payload)
    _wait_shipped(pr_vs, vid)
    assert replication_resends_total.value(reason="duplicate") \
        == before + 1, "the injected duplicate send never happened"
    assert WeedClient(sb_master.url()).download(fid) == payload


def test_partition_holds_watermark_then_heals(mirror):
    _pm, pr_vs, sb_master, _sbv, _tmp = mirror
    # Enough charges that the hold outlives retries; once the WAN
    # breaker opens, sends fail fast without consuming charges.
    fault.arm("wan.partition", "fail*1000")
    payload = b"written during the partition " * 8
    vid, fid, _col = _put(mirror, payload)
    sh = pr_vs.shipper
    _wait(lambda: sh.lag_view()["volumes"]
          .get(str(vid), {}).get("lag_seq", 0) > 0, 10,
          "partition never showed up as lag")
    time.sleep(0.2)  # several ticks: the watermark must hold
    st = _rlog_status(pr_vs, vid)
    assert st["pending"] > 0 and st["acked_seq"] == 0
    fault.disarm_all()
    resilience.reset_breakers()  # the hold opened the WAN breaker
    sh.kick()
    _wait_shipped(pr_vs, vid)
    assert WeedClient(sb_master.url()).download(fid) == payload
    assert sh.lag_view()["volumes"][str(vid)]["lag_seq"] == 0


def test_healthz_degrades_on_lag_slo_breach_and_recovers(mirror):
    pr_master, pr_vs, _sbm, _sbv, _tmp = mirror
    sh = pr_vs.shipper
    vid, _fid, col = _put(mirror, b"slo probe " * 8)
    _wait_shipped(pr_vs, vid)
    pr_vs._send_heartbeat(full=True)
    status, doc = rpc.call_status(f"{pr_master.url()}/cluster/healthz")
    assert status == 200, doc.get("problems")
    assert doc["replication"]["lag_slo"] == 0.05
    sh.paused = True  # WAN maintenance window: journaling continues
    try:
        _put(mirror, b"stuck behind the pause " * 8, collection=col)
        # The paused shipper still OBSERVES lag each tick — pausing
        # shipping must never pause the alarm about it.
        _wait(lambda: sh.lag_view()["volumes"]
              .get(str(vid), {}).get("lag_seconds", 0.0) > 0.05, 10,
              "paused shipper stopped observing lag")
        pr_vs._send_heartbeat(full=True)
        status, doc = rpc.call_status(
            f"{pr_master.url()}/cluster/healthz")
        assert status == 503
        assert any("replication lag" in p and "exceeds SLO" in p
                   for p in doc["problems"]), doc["problems"]
    finally:
        sh.paused = False
        sh.kick()
    _wait_shipped(pr_vs, vid)
    pr_vs._send_heartbeat(full=True)
    status, doc = rpc.call_status(f"{pr_master.url()}/cluster/healthz")
    assert status == 200, doc.get("problems")


def test_raft_leader_failover_with_records_in_flight(mirror):
    """Leadership churn on the primary's master while unshipped
    records sit in the change log: the shipper (volume-server-owned,
    peer-master-addressed) must not lose or skip anything."""
    pr_master, pr_vs, sb_master, _sbv, _tmp = mirror
    fault.arm("wan.partition", "fail*1000")
    payload = b"survives the election " * 8
    vid, fid, _col = _put(mirror, payload)
    raft = pr_master.raft
    with raft._lock:
        raft._become_follower(raft.current_term + 1, None)
    _wait(pr_master.is_leader, 15, "raft never re-elected")
    fault.disarm_all()
    resilience.reset_breakers()
    pr_vs.shipper.kick()
    _wait_shipped(pr_vs, vid)
    assert WeedClient(sb_master.url()).download(fid) == payload
    st = _rlog_status(pr_vs, vid)
    assert st["acked_seq"] == st["last_seq"] > 0


def test_fsck_crc_json_proves_cross_cluster_convergence(mirror):
    """The machine-checkable convergence proof from the README
    runbook: `volume.fsck -crc -json` run against EACH cluster's
    master (same filer namespace) emits a node-address-free checksum
    map; converged clusters compare equal."""
    from seaweedfs_tpu.filer.client import FilerProxy
    from seaweedfs_tpu.filer.server import FilerServer
    pr_master, pr_vs, sb_master, _sbv, _tmp = mirror
    filer = FilerServer(pr_master.url())
    filer.start()
    env_pr = env_sb = None
    try:
        # The filer writes into the default collection: pre-grow and
        # journal-enable so its chunks mirror from the first byte.
        rpc.call(f"{pr_master.url()}/vol/grow?count=2", "POST")
        for loc in pr_vs.store.locations:
            for v in list(loc.volumes.values()):
                if v.rlog is None:
                    v.enable_rlog()
        fp = FilerProxy(filer.url())
        fp.put("/dr/a.txt", b"alpha " * 200)
        fp.put("/dr/deep/b.txt", b"beta " * 333)

        def all_acked():
            doc = rpc.call(f"http://{pr_vs.url()}/debug/replication")
            rlogs = doc.get("rlog") or {}
            return rlogs and all(st["pending"] == 0
                                 for st in rlogs.values())
        _wait(all_acked, 20, "filer chunks never finished shipping")
        env_pr = CommandEnv(pr_master.url(), filer_url=filer.url())
        env_sb = CommandEnv(sb_master.url(), filer_url=filer.url())
        doc_pr = json.loads(run_command(env_pr,
                                        "volume.fsck -crc -json"))
        doc_sb = json.loads(run_command(env_sb,
                                        "volume.fsck -crc -json"))
        assert doc_pr["verdict"] == "ok", doc_pr
        assert doc_sb["verdict"] == "ok", doc_sb
        assert doc_pr["checked"] > 0
        assert doc_pr["volumes"] == doc_sb["volumes"]
    finally:
        for env in (env_pr, env_sb):
            if env is not None:
                env.close()
        filer.stop()


def test_mirror_shell_status_pause_resume(mirror):
    pr_master, pr_vs, sb_master, _sbv, _tmp = mirror
    pr_vs._send_heartbeat(full=True)
    env = CommandEnv(pr_master.url())
    try:
        out = run_command(env, "cluster.mirror.status")
        assert "peer(s):" in out and sb_master.url() in out
        assert "lag SLO: 0.05s" in out
        run_command(env, "cluster.mirror.pause")
        assert pr_vs.shipper.paused
        pr_vs._send_heartbeat(full=True)  # pause state rides heartbeats
        assert "paused:" in run_command(env, "cluster.mirror.status")
        run_command(env, "cluster.mirror.resume")
        assert not pr_vs.shipper.paused
        doc = rpc.call(f"{pr_master.url()}/cluster/mirror")
        assert doc["paired"] and sb_master.url() in doc["peers"]
    finally:
        env.close()


def test_replication_metrics_promcheck(mirror):
    _pm, pr_vs, _sbm, _sbv, _tmp = mirror
    fault.arm("wan.duplicate", "fail*1")  # materialize the resend series
    vid, _fid, _col = _put(mirror, b"promcheck traffic " * 32)
    _wait_shipped(pr_vs, vid)
    text = rpc.call(f"http://{pr_vs.url()}/metrics").decode()
    for fam in ("SeaweedFS_replication_shipped_bytes_total",
                "SeaweedFS_replication_resends_total",
                "SeaweedFS_replication_lag_seconds_total",
                "SeaweedFS_replication_lag_seconds"):
        assert fam in text, f"{fam} missing from /metrics"
    assert validate_exposition(text) == [], validate_exposition(text)[:5]


# -- function-scoped chaos: restarts + cutover under load --------------------

def test_restart_both_sides_resumes_from_watermarks(tmp_path):
    """Standby dies mid-backlog, comes back on the same port + dir:
    the `.rap` applied watermark no-ops any re-shipped prefix.  Then
    the primary restarts: the volume re-enables its change log from
    the sidecar on mount and the shipper resumes from the durable
    `.rwm` — nothing is lost, nothing re-ships."""
    sb_master = MasterServer(volume_size_limit_mb=16,
                             meta_dir=str(tmp_path / "sbmeta"),
                             pulse_seconds=60)
    sb_master.start()
    (tmp_path / "sb").mkdir()
    sb_port = rpc.free_port()

    def new_sb_vs():
        return VolumeServer(sb_master.url(), [str(tmp_path / "sb")],
                            port=sb_port, max_volume_counts=[50],
                            pulse_seconds=60)
    pr_master = MasterServer(volume_size_limit_mb=16,
                             meta_dir=str(tmp_path / "prmeta"),
                             pulse_seconds=60)
    pr_master.start()
    (tmp_path / "pr").mkdir()
    pr_port = rpc.free_port()

    def new_pr_vs():
        return VolumeServer(pr_master.url(), [str(tmp_path / "pr")],
                            port=pr_port, max_volume_counts=[50],
                            pulse_seconds=60,
                            replicate_peer=sb_master.url(),
                            replicate_interval=0.05)
    sb_vs = new_sb_vs()
    sb_vs.start()
    pr_vs = new_pr_vs()
    pr_vs.start()
    live = [pr_vs, sb_vs]
    try:
        rpc.call(f"{pr_master.url()}/vol/grow?count=1"
                 "&collection=restart", "POST")
        payloads = {}

        def put(data):
            a = rpc.call(f"{pr_master.url()}/dir/assign"
                         "?collection=restart")
            vid = int(a["fid"].split(",")[0])
            v = live[0].store.find_volume(vid)
            if v.rlog is None:
                v.enable_rlog()
            rpc.call(f"http://{a['url']}/{a['fid']}", "POST", data)
            payloads[a["fid"]] = data
            return vid

        vid = put(b"before the outage " * 16)
        _wait_shipped(pr_vs, vid)
        # Standby goes away; acked writes keep landing on the primary.
        sb_vs.stop()
        for i in range(3):
            put(f"during the outage {i} ".encode() * 16)
        v = pr_vs.store.find_volume(vid)
        _wait(lambda: v.rlog.pending() >= 3, 10)
        time.sleep(0.2)
        assert v.rlog.pending() >= 3, "watermark must hold while down"
        # Standby returns on the same port + dir and catches up.
        sb_vs = new_sb_vs()
        live[1] = sb_vs
        sb_vs.start()
        resilience.reset_breakers()  # the outage opened the breaker
        pr_vs.shipper.kick()
        _wait_shipped(pr_vs, vid, timeout=30)
        acked_before_restart = v.rlog.acked_seq
        # Primary restarts: same dir, same peer.
        pr_vs.stop()
        pr_vs = new_pr_vs()
        live[0] = pr_vs
        pr_vs.start()
        v = pr_vs.store.find_volume(vid)
        assert v.rlog is not None, \
            "mount must re-enable the change log from the sidecar"
        assert v.rlog.acked_seq == acked_before_restart
        assert v.rlog.pending() == 0, "nothing may re-ship after ack"
        vid2 = put(b"after the restart " * 16)
        assert vid2 == vid
        _wait_shipped(pr_vs, vid, timeout=30)
        sbc = WeedClient(sb_master.url())
        for fid, data in payloads.items():
            assert sbc.download(fid) == data
    finally:
        for s in live:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — already stopped
                pass
        pr_master.stop()
        sb_master.stop()


def test_cutover_under_load_zero_client_visible_errors(tmp_path):
    """The acceptance drill: live writers during cluster.mirror.cutover
    see zero errors (failing over to the standby master when the
    drained primary refuses them), and every write EITHER cluster
    acked is readable from the standby afterwards — zero acked-write
    loss."""
    sb_master = MasterServer(volume_size_limit_mb=16,
                             meta_dir=str(tmp_path / "sbmeta"),
                             pulse_seconds=60)
    sb_master.start()
    (tmp_path / "sb").mkdir()
    sb_vs = VolumeServer(sb_master.url(), [str(tmp_path / "sb")],
                         max_volume_counts=[50], pulse_seconds=60)
    sb_vs.start()
    pr_master = MasterServer(volume_size_limit_mb=16,
                             meta_dir=str(tmp_path / "prmeta"),
                             pulse_seconds=60)
    pr_master.start()
    (tmp_path / "pr").mkdir()
    pr_vs = VolumeServer(pr_master.url(), [str(tmp_path / "pr")],
                         max_volume_counts=[50], pulse_seconds=60,
                         replicate_peer=sb_master.url(),
                         replicate_interval=0.05)
    pr_vs.start()
    env = None
    stop = threading.Event()
    th = None
    try:
        # Pre-grow + journal-enable the load collection so every
        # writer needle is shipped from the first byte.
        rpc.call(f"{pr_master.url()}/vol/grow?count=1&collection=cut",
                 "POST")
        a = rpc.call(f"{pr_master.url()}/dir/assign?collection=cut")
        pr_vs.store.find_volume(
            int(a["fid"].split(",")[0])).enable_rlog()
        rpc.call(f"http://{a['url']}/{a['fid']}", "POST",
                 b"cutover seed")
        acked, errors = [], []

        def writer():
            pc = WeedClient(pr_master.url())
            sc = WeedClient(sb_master.url())
            i = 0
            while not stop.is_set():
                data = f"cutover payload {i} ".encode() * 8
                i += 1
                try:
                    # Failover clients write to a standby-local
                    # collection: each cluster allocates needle keys
                    # independently, so mixing both write paths into
                    # one mirrored volume would collide.
                    try:
                        fid = pc.upload_data(data, collection="cut")
                    except Exception:  # noqa: BLE001 — drained away
                        fid = sc.upload_data(data, collection="cutsb")
                    acked.append((fid, data))
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                time.sleep(0.005)

        th = threading.Thread(target=writer, daemon=True)
        th.start()
        time.sleep(0.4)  # some primary-acked traffic first
        env = CommandEnv(pr_master.url())
        run_command(env, "lock")
        out = run_command(env,
                          "cluster.mirror.cutover -grace 1 -timeout 30")
        time.sleep(0.3)  # post-cutover writes keep flowing (standby)
        stop.set()
        th.join(timeout=15)
        assert not th.is_alive()
        assert "cutover complete" in out
        assert pr_vs.shipper.paused, \
            "cutover must quiesce the old primary's shipper"
        assert errors == [], errors[:3]
        assert len(acked) > 5
        # Zero acked-write loss: EVERY acked write — landed on the
        # primary before/during the drain or on the standby after —
        # reads back byte-identical from the standby cluster.
        sbc = WeedClient(sb_master.url())
        for fid, data in acked:
            assert sbc.download(fid) == data
    finally:
        stop.set()
        if th is not None:
            th.join(timeout=15)
        if env is not None:
            env.close()
        for s in (pr_vs, sb_vs):
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — drained/stopped
                pass
        pr_master.stop()
        sb_master.stop()
