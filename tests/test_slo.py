"""Workload SLO plane: quantile sketch accuracy/merge/window-roll
(deterministic injected clock — no sleeps), space-saving heavy-hitter
properties, burn-rate engine, the rpc histogram's new status-class +
endpoint-family labels, /debug/slow exemplars linking to /debug/traces,
/debug/hot + cluster.hot, cross-process aggregation on
/cluster/healthz, the duplicate-registration regression, and live
promcheck-gated scrapes of every new instrument on all three roles."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import events, fault
from seaweedfs_tpu.cluster import rpc
from seaweedfs_tpu.cluster.client import WeedClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.stats.hotkeys import HotKeyTracker, SpaceSaving
from seaweedfs_tpu.stats.promcheck import validate_exposition
from seaweedfs_tpu.stats.sketch import QuantileSketch, WindowedSketch
from seaweedfs_tpu.stats.slo import (SloObjectives, SloTracker,
                                     merge_sketch_dicts)

pytestmark = pytest.mark.slo


# -- quantile sketch: documented accuracy bound ------------------------------

def _check_bound(values, alpha=0.01):
    """The sketch's documented guarantee: the reported q-quantile is
    within relative error alpha of the true (nearest-rank) q-quantile.
    A hair of slack covers the nearest-rank-vs-interpolation delta at
    rank boundaries."""
    sk = QuantileSketch(alpha=alpha)
    for v in values:
        sk.observe(v)
    arr = np.sort(np.asarray(values))
    for q in (0.01, 0.1, 0.5, 0.9, 0.95, 0.99, 0.999):
        est = sk.quantile(q)
        true = float(arr[max(0, int(np.ceil(q * len(arr))) - 1)])
        assert abs(est - true) <= alpha * true + 1e-12, \
            (q, est, true, abs(est - true) / true)


def test_sketch_accuracy_heavy_tail():
    rng = np.random.default_rng(7)
    _check_bound(rng.pareto(1.5, 50000) * 1e-3 + 1e-5)


def test_sketch_accuracy_bimodal():
    rng = np.random.default_rng(8)
    fast = rng.lognormal(-8.0, 0.3, 40000)    # ~0.3ms mode
    slow = rng.lognormal(-2.0, 0.4, 1000)     # ~135ms tail mode
    _check_bound(np.concatenate([fast, slow]))


def test_sketch_accuracy_lognormal_and_constant():
    rng = np.random.default_rng(9)
    _check_bound(rng.lognormal(-7.0, 1.5, 30000))
    _check_bound(np.full(1000, 0.0042))


def test_sketch_zero_and_empty():
    sk = QuantileSketch()
    assert sk.quantile(0.5) is None
    sk.observe(0.0)          # below min_value -> zero bucket
    sk.observe(1e-9)
    assert sk.quantile(0.5) == sk.min_value
    assert sk.count == 2


def test_sketch_merge_equals_concatenated_stream():
    rng = np.random.default_rng(10)
    a, b = rng.pareto(2.0, 5000) * 1e-3, rng.lognormal(-6, 1, 5000)
    whole = QuantileSketch()
    for v in np.concatenate([a, b]):
        whole.observe(v)
    left, right = QuantileSketch(), QuantileSketch()
    for v in a:
        left.observe(v)
    for v in b:
        right.observe(v)
    left.merge(right)
    assert left.count == whole.count
    for q in (0.05, 0.5, 0.95, 0.99):
        assert left.quantile(q) == whole.quantile(q)  # merge is exact


def test_sketch_merge_parameter_mismatch_raises():
    with pytest.raises(ValueError):
        QuantileSketch(alpha=0.01).merge(QuantileSketch(alpha=0.02))


def test_sketch_wire_roundtrip_and_dict_merge():
    rng = np.random.default_rng(11)
    sketches, dicts = [], []
    for _ in range(3):
        sk = QuantileSketch()
        for v in rng.lognormal(-6, 1, 2000):
            sk.observe(v)
        sketches.append(sk)
        dicts.append(sk.to_dict())
    # Roundtrip is lossless.
    rt = QuantileSketch.from_dict(dicts[0])
    assert rt.quantile(0.99) == sketches[0].quantile(0.99)
    assert rt.count == sketches[0].count
    # Cross-process aggregation: merging the wire dicts equals merging
    # the live sketches.
    merged = merge_sketch_dicts(dicts)
    live = QuantileSketch()
    for sk in sketches:
        live.merge(sk)
    assert merged.count == live.count
    assert merged.quantile(0.95) == live.quantile(0.95)
    # Mismatched/garbage entries are skipped, not fatal — including
    # structurally malformed payloads from buggy/mixed-version peers
    # (healthz must never 500 on a bad heartbeat).
    assert merge_sketch_dicts([{"junk": 1}, dicts[0]]).count == 2000
    assert merge_sketch_dicts(
        [{"buckets": [1, 2]}, {"buckets": "zzz", "alpha": 0.01},
         {"alpha": "NaN is fine", "buckets": {"1": "x"}},
         dicts[0]]).count == 2000
    assert merge_sketch_dicts([]) is None


def test_windowed_sketch_rolls_with_injected_clock():
    t = [0.0]
    w = WindowedSketch(window=60.0, slices=6, clock=lambda: t[0])
    for _ in range(100):
        w.observe(0.001)
    t[0] = 30.0
    for _ in range(100):
        w.observe(1.0)
    assert w.count() == 200           # both slices live
    assert w.quantile(0.25) < 0.01
    t[0] = 65.0                        # t=0 slice expired, t=30 lives
    assert w.count() == 100
    assert w.quantile(0.5) == pytest.approx(1.0, rel=0.02)
    t[0] = 200.0                       # everything expired
    assert w.count() == 0 and w.quantile(0.5) is None
    # Ring reuse after a long idle gap must not resurrect old epochs.
    w.observe(0.5)
    assert w.count() == 1


# -- space-saving heavy hitters ----------------------------------------------

def test_space_saving_exact_when_under_capacity():
    ss = SpaceSaving(capacity=64)
    rng = np.random.default_rng(12)
    truth: dict[int, int] = {}
    for k in rng.integers(0, 40, 5000):
        ss.offer(int(k))
        truth[int(k)] = truth.get(int(k), 0) + 1
    for row in ss.top(64):
        assert row["error"] == 0
        assert row["count"] == truth[row["key"]]


def test_space_saving_bounded_error_under_zipf():
    capacity, n = 64, 50000
    ss = SpaceSaving(capacity=capacity)
    rng = np.random.default_rng(13)
    ranks = np.arange(1, 5001)
    probs = 1.0 / ranks ** 1.2
    probs /= probs.sum()
    keys = rng.choice(ranks, size=n, p=probs)
    truth: dict[int, int] = {}
    for k in keys:
        ss.offer(int(k))
        truth[int(k)] = truth.get(int(k), 0) + 1
    top = ss.top(capacity)
    min_count = min(row["count"] for row in top)
    for row in top:
        true = truth.get(row["key"], 0)
        # count overestimates by at most the recorded error, which is
        # itself bounded by the evicted minimum <= N/capacity.
        assert true <= row["count"] <= true + row["error"]
        assert row["error"] <= min_count <= n / capacity + min_count
    # The true heavy hitters survive: every key with frequency above
    # N/capacity is guaranteed present.
    tracked = {row["key"] for row in top}
    for key, cnt in truth.items():
        if cnt > n / capacity:
            assert key in tracked, (key, cnt)


def test_hot_key_tracker_snapshot_shape():
    hk = HotKeyTracker(capacity=8)
    for _ in range(5):
        hk.read(3, 0x172, "10.0.0.1")
    hk.write(4, 0x9, "10.0.0.2")
    snap = hk.snapshot(k=4)
    assert snap["dimensions"]["volume"]["read"]["top"][0]["key"] == 3
    assert snap["dimensions"]["needle"]["read"]["top"][0]["key"] \
        == "3,172"
    assert snap["dimensions"]["client"]["write"]["top"][0]["key"] \
        == "10.0.0.2"
    hk.clear()
    assert hk.snapshot()["dimensions"]["volume"]["read"]["total"] == 0


# -- burn-rate engine (deterministic clock) ----------------------------------

def _tracker(clock, **obj):
    tr = SloTracker("t", node="t:1", clock=clock, short_window=60.0,
                    long_window=360.0)
    tr.set_objectives(**obj)
    return tr


def test_undeclared_objectives_never_burn():
    t = [100.0]
    tr = _tracker(lambda: t[0])
    for _ in range(50):
        tr.observe("/needle", "GET", 500, 2.0)
    state = tr.burn_state()
    assert not state["declared"] and not state["fast_burn"]


def test_availability_fast_burn_and_recovery():
    t = [100.0]
    tr = _tracker(lambda: t[0], availability=0.999)
    before = events.events_total.value(type="slo.burn")
    for i in range(40):
        tr.observe("/needle", "GET", 500 if i % 2 else 200, 0.001)
    state = tr.burn_state()
    # 50% errors / 0.1% budget = 500x burn in both windows.
    assert state["fast_burn"]
    assert state["availability"]["short"]["burn"] >= 14.4
    assert events.events_total.value(type="slo.burn") == before + 1
    # Episode semantics: still burning -> no second event.
    tr.burn_state()
    assert events.events_total.value(type="slo.burn") == before + 1
    # Errors stop; the short window expires -> burn clears (min of the
    # two windows gates the verdict).
    t[0] += 70.0
    for _ in range(20):
        tr.observe("/needle", "GET", 200, 0.001)
    state = tr.burn_state()
    assert not state["fast_burn"]
    # A fresh episode emits again.
    for _ in range(40):
        tr.observe("/needle", "GET", 500, 0.001)
    assert tr.burn_state()["fast_burn"]
    assert events.events_total.value(type="slo.burn") == before + 2


def test_latency_burn_counts_slow_reads_only():
    """The read-p99 burn divides by READS: a write-heavy workload
    (10 slow reads among 90 writes) must still fast-burn — writes in
    the denominator would dilute a total read collapse to 10x and
    never page."""
    t = [50.0]
    tr = _tracker(lambda: t[0], read_p99=0.010)
    for _ in range(10):
        tr.observe("/needle", "GET", 200, 0.050)   # all reads slow
    for _ in range(90):
        tr.observe("/needle", "POST", 200, 0.050)  # writes don't count
    state = tr.burn_state()
    assert state["fast_burn"]
    lat = state["latency"]
    assert lat["short"]["breaching"] == 10
    assert lat["short"]["total"] == 10  # denominator is reads, not ops


def test_sheds_do_not_pollute_latency_sketches():
    """A 429 shed is refused before execution: it must not enter the
    aggregate read/write tails (a shedding storm would fake a great
    p50) nor the error-rate denominator — only the shed column."""
    t = [20.0]
    tr = _tracker(lambda: t[0], availability=0.999)
    tr.observe("/needle", "GET", 200, 0.020)
    for _ in range(50):
        tr.observe("/needle", "GET", 429, 0.0)
    agg = tr.agg_quantiles("read")
    assert agg["count"] == 1
    assert agg["p50"] == pytest.approx(0.020, rel=0.03)
    st = tr.burn_state()["availability"]["short"]
    assert st["shed"] == 50
    assert st["total"] == 1 and st["breaching"] == 0


def test_burn_needs_minimum_traffic():
    t = [10.0]
    tr = _tracker(lambda: t[0], availability=0.999)
    for _ in range(SloTracker.MIN_WINDOW_REQUESTS - 1):
        tr.observe("/needle", "GET", 500, 0.001)
    assert not tr.burn_state()["fast_burn"]


def test_control_plane_excluded_from_burn_and_agg():
    t = [10.0]
    tr = _tracker(lambda: t[0], availability=0.999)
    for _ in range(50):
        tr.observe("/admin/scrub", "POST", 500, 0.001)
        tr.observe("/debug/*", "GET", 500, 0.001)
    state = tr.burn_state()
    assert not state["fast_burn"]
    assert state["availability"]["short"]["total"] == 0
    assert tr.agg_quantiles("read")["count"] == 0
    # ...but the per-family sketches still see them.
    assert "/admin/scrub 5xx" in tr.snapshot()["families"]


def test_objectives_validation():
    assert SloObjectives(availability=99.9).availability == \
        pytest.approx(0.999)
    with pytest.raises(ValueError):
        SloObjectives(read_p99=-1.0)
    assert not SloObjectives().declared


def test_exemplars_ring_is_bounded_newest_first():
    t = [5.0]
    tr = SloTracker("t", clock=lambda: t[0], exemplar_capacity=4)
    tr.set_objectives(read_p99=0.001)
    for i in range(10):
        tr.observe("/needle", "GET", 200, 0.5, trace_id=f"tid{i}")
    ex = tr.exemplars(10)
    assert len(ex) == 4 and tr.exemplars_recorded == 10
    assert [e["trace_id"] for e in ex] == \
        ["tid9", "tid8", "tid7", "tid6"]
    assert ex[0]["seconds"] == 0.5


# -- rpc middleware: labels, family normalization, sheds ---------------------

def test_endpoint_family_bounds_cardinality():
    assert rpc.endpoint_family("/dir/assign", literal=True) == \
        "/dir/assign"
    # Real admin endpoints are literal routes and keep their path;
    # an UNMOUNTED /admin/<x> is a client-chosen string (on gateways
    # the whole / namespace is) and must not mint a label.
    assert rpc.endpoint_family("/admin/ec/generate", literal=True) == \
        "/admin/ec/generate"
    assert rpc.endpoint_family("/admin/minted-by-client-7",
                               literal=False) == "/other"
    assert rpc.endpoint_family("/3,0172cb7d88", literal=False) == \
        "/needle"
    assert rpc.endpoint_family("/3,0172cb7d88/img.jpg",
                               literal=False) == "/needle"
    assert rpc.endpoint_family("/debug/whatever", literal=False) == \
        "/debug/*"
    assert rpc.endpoint_family("/any/user/path.txt", literal=False) == \
        "/other"


def test_request_histogram_status_and_family_labels():
    server = rpc.JsonHttpServer()
    server.route("GET", "/admin/thing", lambda q, b: {"ok": 1})

    def boom(q, b):
        raise RuntimeError("kaboom")
    server.route("GET", "/boom", boom)

    def missing(q, b):
        raise rpc.RpcError(404, "nope")
    server.route("GET", "/gone", missing)
    server.prefix_route("GET", "/", lambda p, q, b: {"path": p})
    reg = server.enable_metrics("labeltest")
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        rpc.call(f"{base}/admin/thing")
        rpc.call(f"{base}/3,0172abcd")        # prefix -> /needle
        rpc.call(f"{base}/some/user/file")    # prefix -> /other
        with pytest.raises(rpc.RpcError):
            rpc.call(f"{base}/boom")
        with pytest.raises(rpc.RpcError):
            rpc.call(f"{base}/gone")
        text = reg.expose()
        assert ('SeaweedFS_labeltest_request_seconds_bucket{'
                'family="/admin/thing"') in text
        assert 'family="/needle"' in text
        assert 'family="/other"' in text
        assert 'family="/boom",le="+Inf",status="5xx"' in text
        assert 'family="/gone",le="+Inf",status="4xx"' in text
        # The counter keeps its reference shape (stats/metrics.go).
        assert 'SeaweedFS_labeltest_request_total{type="GET"} 5' in text
        assert validate_exposition(text) == []
        # The SLO tracker saw the same requests, split by status class.
        fams = server.slo.snapshot()["families"]
        assert "/boom 5xx" in fams and "/gone 4xx" in fams
        assert fams["/needle 2xx"]["count"] == 1
    finally:
        server.stop()


def test_admission_shed_lands_in_error_tail():
    """A shed 429 is part of the observable error tail: it shows up in
    the labeled histogram and the SLO shed column."""
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(1, queue_depth=0,
                                       queue_timeout=0.05))
    server.route("GET", "/slow",
                 lambda q, b: (time.sleep(0.4), {"ok": True})[1])
    reg = server.enable_metrics("shedtest")
    server.slo.set_objectives(availability=0.999)
    server.start()
    statuses = []

    def call_slow():
        try:
            rpc.call(f"http://127.0.0.1:{server.port}/slow",
                     timeout=5.0)
            statuses.append(200)
        except rpc.RpcError as e:
            statuses.append(e.status)
    try:
        threads = [threading.Thread(target=call_slow)
                   for _ in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert 429 in statuses
        text = reg.expose()
        assert 'family="/slow",le="+Inf",status="4xx"' in text
        burn = server.slo.burn_state()
        assert burn["availability"]["short"]["shed"] >= 1
        # Sheds are reported but never counted as budget burn.
        assert burn["availability"]["short"]["breaching"] == 0
    finally:
        server.stop()


# -- duplicate-registration regression ---------------------------------------

def test_enable_metrics_idempotent_no_duplicate_families():
    """Re-initializing metrics on a live server (rolling-restart /
    re-init paths re-create registries) must not stack duplicate
    exposition families — promcheck treats a duplicate TYPE as a
    corrupt scrape."""
    server = rpc.JsonHttpServer()
    reg1 = server.enable_metrics("duptest")
    reg2 = server.enable_metrics("duptest")
    assert reg1 is reg2
    from seaweedfs_tpu.stats.metrics import (ec_stage_bytes,
                                             ec_stage_seconds)
    for _ in range(2):  # process-global singletons re-registered
        reg1.register_once(ec_stage_seconds)
        reg1.register_once(ec_stage_bytes)
    text = reg1.expose()
    assert text.count("# TYPE SeaweedFS_duptest_request_total") == 1
    assert text.count("# TYPE SeaweedFS_request_quantile_seconds") == 1
    assert text.count("# TYPE SeaweedFS_ec_stage_seconds") == 1
    assert validate_exposition(text) == []


def test_in_process_server_restart_scrape_stays_clean(tmp_path):
    """A volume server stopped and re-created in one process (the
    rolling-restart tests' pattern) re-registers every process-global
    instrument into a fresh registry; the new scrape must stay
    promcheck-clean with no duplicated families."""
    master = MasterServer(volume_size_limit_mb=16,
                          meta_dir=str(tmp_path / "meta"),
                          pulse_seconds=60)
    master.start()
    try:
        d = tmp_path / "vs"
        d.mkdir()
        vs1 = VolumeServer(master.url(), [str(d)], pulse_seconds=60)
        vs1.start()
        client = WeedClient(master.url())
        fid = client.upload_data(b"restart payload")
        client.download(fid)
        vs1.stop()
        vs2 = VolumeServer(master.url(), [str(d)], pulse_seconds=60)
        vs2.start()
        try:
            client2 = WeedClient(master.url())
            client2.download(fid)
            text = rpc.call(f"http://{vs2.url()}/metrics").decode()
            assert validate_exposition(text) == [], \
                validate_exposition(text)[:5]
            for fam in ("SeaweedFS_ec_stage_seconds",
                        "SeaweedFS_request_quantile_seconds",
                        "SeaweedFS_requests_shed_total"):
                assert text.count(f"# TYPE {fam}") == 1, fam
        finally:
            vs2.stop()
    finally:
        master.stop()


# -- mini-cluster: live scrapes, aggregation, hot keys, acceptance -----------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """Master + two volume servers + filer in one process, tracing
    recording on (exemplars must carry resolvable trace ids)."""
    saved = {k: os.environ.get(k)
             for k in ("SEAWEEDFS_TPU_TRACES", "SEAWEEDFS_TPU_TRACE")}
    os.environ["SEAWEEDFS_TPU_TRACES"] = "1"
    os.environ.pop("SEAWEEDFS_TPU_TRACE", None)
    tmp = tmp_path_factory.mktemp("slo-cluster")
    master = MasterServer(volume_size_limit_mb=16,
                          meta_dir=str(tmp / "meta"), pulse_seconds=60)
    master.start()
    servers = []
    for i in range(2):
        d = tmp / f"vs{i}"
        d.mkdir()
        vs = VolumeServer(master.url(), [str(d)],
                          max_volume_counts=[100], pulse_seconds=60,
                          slo_read_p99=0.5, slo_availability=0.999)
        vs.start()
        servers.append(vs)
    from seaweedfs_tpu.filer.server import FilerServer
    filer = FilerServer(master.url(), metrics_port=0)
    filer.start()
    client = WeedClient(master.url())
    yield master, servers, filer, client
    filer.stop()
    for vs in servers:
        vs.stop()
    master.stop()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_live_scrape_new_instruments_all_roles(cluster):
    """promcheck-gated live scrape of every new instrument —
    SeaweedFS_request_quantile_seconds, SeaweedFS_slo_burn_rate, and
    the labeled request histogram — on master, volume server, and the
    filer's metrics port."""
    master, servers, filer, client = cluster
    from seaweedfs_tpu.filer.client import FilerProxy
    fid = client.upload_data(b"slo scrape payload " * 8)
    for _ in range(3):
        client.download(fid)
    FilerProxy(filer.url()).put("/slo/f.txt", b"filer traffic")
    scrapes = {
        "master": rpc.call(f"{master.url()}/metrics").decode(),
        "volume": rpc.call(
            f"http://{servers[0].url()}/metrics").decode(),
        "filer": rpc.call(
            f"{filer.metrics_server.url()}/metrics").decode(),
    }
    for role, text in scrapes.items():
        assert validate_exposition(text) == [], \
            (role, validate_exposition(text)[:5])
        assert "SeaweedFS_request_quantile_seconds" in text, role
        assert "SeaweedFS_slo_burn_rate" in text, role
        assert 'status="2xx"' in text, role
    assert 'q="0.99"' in scrapes["volume"]
    # Burn gauge carries live values on the volume role (objectives
    # declared there).
    assert ('SeaweedFS_slo_burn_rate{role="volumeServer",'
            'slo="availability",window="short"}') in scrapes["volume"]


def test_healthz_aggregates_node_sketches(cluster):
    """Window-roll + cross-process aggregation: every node ships its
    mergeable read/write sketches in heartbeats; /cluster/healthz
    folds them (plus the master's own) into one cluster-wide tail."""
    master, servers, _filer, client = cluster
    fid = client.upload_data(b"aggregation payload")
    for _ in range(4):
        client.download(fid)
    for vs in servers:
        vs._send_heartbeat(full=True)
    status, doc = rpc.call_status(f"{master.url()}/cluster/healthz")
    assert status == 200, doc.get("problems")
    slo_doc = doc["slo"]
    # master + both volume servers contribute sketches.
    assert slo_doc["sources"] == 3
    assert slo_doc["read"]["count"] >= 4
    assert slo_doc["read"]["p99"] > 0
    # The merged count equals the sum of the contributors' live
    # aggregate counts at heartbeat time (merge is exact addition) —
    # node sketches are heartbeat snapshots, so recompute from them.
    node_counts = sum(
        getattr(dn, "slo_state", {}).get("read", {}).get("count", 0)
        for dn in master.topo.leaves())
    own = master.server.slo.agg_quantiles("read")["count"]
    assert slo_doc["read"]["count"] >= node_counts
    assert slo_doc["read"]["count"] <= node_counts + own
    # Node rows carry their burn verdict.
    assert all("slo" in n for n in doc["nodes"])


def test_dead_node_slo_state_excluded_from_rollup(cluster):
    """A dead node's final heartbeat verdict must not haunt the live
    rollup: its fast-burn problem and its last-window sketch drop out
    of /cluster/healthz once the heartbeat goes stale."""
    master, servers, _filer, _client = cluster
    dn = next(d for d in master.topo.leaves()
              if d.url() == servers[1].url())
    poisoned = {"declared": True, "fast_burn": True,
                "slow_burn": False,
                "read": {"alpha": 0.01, "min_value": 1e-6,
                         "count": 10 ** 9, "sum": 1.0, "zero": 0,
                         "buckets": {"600": 10 ** 9}}}
    saved_seen = dn.last_seen
    try:
        dn.slo_state = poisoned
        _st, doc = rpc.call_status(f"{master.url()}/cluster/healthz")
        assert any("SLO fast burn" in p for p in doc["problems"])
        assert doc["slo"]["read"]["count"] >= 10 ** 9
        dn.last_seen = 0.0  # node dies; verdict must die with it
        _st, doc = rpc.call_status(f"{master.url()}/cluster/healthz")
        assert not any("SLO fast burn" in p for p in doc["problems"])
        assert doc["slo"]["read"]["count"] < 10 ** 9
    finally:
        dn.last_seen = saved_seen
        servers[1]._send_heartbeat(full=True)  # restore real state


def test_debug_hot_and_cluster_hot_shell(cluster):
    """Skewed reads surface the hot needle/volume/client on /debug/hot
    and the merged shell view."""
    from seaweedfs_tpu.shell import CommandEnv, run_command
    master, servers, _filer, client = cluster
    hot_fid = client.upload_data(b"hot needle " * 4)
    cold_fid = client.upload_data(b"cold needle " * 4)
    for _ in range(12):
        client.download(hot_fid)
    client.download(cold_fid)
    hot_vid = int(hot_fid.split(",")[0])
    holder = next(vs for vs in servers
                  if vs.store.find_volume(hot_vid) is not None)
    out = rpc.call(f"http://{holder.url()}/debug/hot?k=4")
    top_needles = out["dimensions"]["needle"]["read"]["top"]
    # the tracker keys needles as "vid,hexkey" (no cookie)
    assert top_needles[0]["key"].startswith(f"{hot_vid},")
    assert top_needles[0]["count"] >= 12
    assert out["dimensions"]["volume"]["read"]["top"][0]["count"] >= 12
    assert out["dimensions"]["client"]["read"]["top"][0]["key"] == \
        "127.0.0.1"
    env = CommandEnv(master.url())
    try:
        text = run_command(env, "cluster.hot -k 5")
        assert "volume (read" in text and "needle (read" in text
        assert "127.0.0.1" in text
        text = run_command(env, "cluster.hot -k 3 -dimension client")
        assert "volume (read" not in text and "client (read" in text
    finally:
        env.close()
    # reset starts a fresh observation window
    out = rpc.call(f"http://{holder.url()}/debug/hot?reset=1")
    out = rpc.call(f"http://{holder.url()}/debug/hot")
    assert out["dimensions"]["needle"]["read"]["total"] == 0


def test_acceptance_slow_fault_exemplar_trace_burn_healthz(tmp_path):
    """The ISSUE acceptance flow end-to-end, in-process: an injected
    slow fault on the volume read path produces a /debug/slow exemplar
    whose trace id resolves in /debug/traces, flips /cluster/healthz
    to degraded via the latency burn rate, and emits slo.burn."""
    saved = {k: os.environ.get(k)
             for k in ("SEAWEEDFS_TPU_TRACES", "SEAWEEDFS_TPU_TRACE")}
    os.environ["SEAWEEDFS_TPU_TRACES"] = "1"
    os.environ.pop("SEAWEEDFS_TPU_TRACE", None)
    master = MasterServer(volume_size_limit_mb=16,
                          meta_dir=str(tmp_path / "meta"),
                          pulse_seconds=60)
    master.start()
    d = tmp_path / "vs"
    d.mkdir()
    vs = VolumeServer(master.url(), [str(d)], pulse_seconds=60,
                      slo_read_p99=0.010, slo_availability=0.99)
    vs.start()
    try:
        client = WeedClient(master.url())
        fid = client.upload_data(b"slow fault payload " * 8)
        burn_before = events.events_total.value(type="slo.burn")
        fault.arm("volume.read", "delay:0.05")
        try:
            for _ in range(15):
                client.download(fid)
        finally:
            fault.disarm_all()
        # 1) /debug/slow carries exemplars above the 10ms objective...
        slow = rpc.call(f"http://{vs.url()}/debug/slow")
        assert slow["threshold_seconds"] == 0.010
        exemplars = [e for e in slow["exemplars"]
                     if e["family"] == "/needle"]
        assert len(exemplars) >= 15
        assert all(e["seconds"] >= 0.05 for e in exemplars[:15])
        # 2) ...whose trace id resolves to real spans in /debug/traces.
        tid = exemplars[0]["trace_id"]
        assert tid
        trace = rpc.call(
            f"http://{vs.url()}/debug/traces?trace={tid}")
        assert trace["trace_id"] == tid and trace["spans"]
        assert any(s["service"] == "volumeServer"
                   for s in trace["spans"])
        # 3) the latency burn flips /cluster/healthz to degraded...
        vs._send_heartbeat(full=True)
        status, doc = rpc.call_status(
            f"{master.url()}/cluster/healthz")
        assert status == 503 and not doc["healthy"]
        assert any("SLO fast burn" in p for p in doc["problems"]), \
            doc["problems"]
        assert vs.url() in doc["slo"]["fast_burn"]
        # 4) ...and slo.burn landed in the journal with a trace id.
        assert events.events_total.value(type="slo.burn") > burn_before
        evs = events.JOURNAL.snapshot(type_="slo.burn")
        assert evs and evs[-1]["attrs"]["slo"] == "latency"
        assert evs[-1]["trace_id"]
    finally:
        vs.stop()
        master.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
