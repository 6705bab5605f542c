"""Time-attribution plane: per-request phase budgets (stats/phases.py),
lock-contention metering (stats/contention.py), the always-on
continuous profiler (utils/pprof.py), and cluster.profile merging.

The load-bearing invariants:

- a slow request's exemplar carries a phase budget whose non-queue
  phases sum to (approximately all of) its measured wall;
- admission-queue wait is attributed to the `queue` phase;
- a contended MeteredLock records the wait in the histogram AND the
  waiting request's `lock` phase, while /debug/locks names the holder
  and waiters with stacks;
- the disarmed/uncontended metered fast path stays cheap (the fault-
  registry stance: zero-cost when off);
- `?window=` profiles answer instantly from the ring, `?seconds=` is
  validated and clamped;
- every new instrument survives a promcheck-gated live scrape on all
  three roles;
- cluster.profile merges collapsed stacks from >= 2 distinct nodes of
  a real subprocess cluster.
"""

import os
import threading
import time

import pytest

from seaweedfs_tpu.cluster import rpc
from seaweedfs_tpu.stats import contention, phases
from seaweedfs_tpu.stats.promcheck import validate_exposition

pytestmark = pytest.mark.attribution


@pytest.fixture(scope="module", autouse=True)
def _stop_continuous_profiler():
    """The continuous profiler is a process-wide singleton: left
    running it would keep sampling (and allocating) through every
    LATER test module, skewing timing- and tracemalloc-sensitive
    tests elsewhere in the suite."""
    yield
    from seaweedfs_tpu.utils import pprof
    if pprof.PROFILER is not None:
        pprof.PROFILER.stop()


# -- phase ledger ------------------------------------------------------------

def test_phase_ledger_sums_to_wall_on_slow_request():
    """The budget invariant: named phases + the handler residual cover
    the dispatch wall, and the budget rides the /debug/slow exemplar."""
    server = rpc.JsonHttpServer()

    def slowop(q, b):
        with phases.phase("disk"):
            time.sleep(0.12)
        with phases.phase("rpc_downstream"):
            time.sleep(0.08)
        time.sleep(0.08)  # handler residual
        return {"ok": True}

    server.route("GET", "/slowop", slowop)
    server.enable_metrics("phasetest")
    server.start()
    try:
        assert rpc.call(f"http://127.0.0.1:{server.port}/slowop") == \
            {"ok": True}
        ex = server.slo.exemplars()
        assert ex, "a 0.28s request must exemplar (threshold 0.25)"
        ph = ex[0]["phases"]
        wall = ex[0]["seconds"]
        covered = sum(v for k, v in ph.items() if k != "queue")
        assert covered >= 0.9 * wall
        assert covered <= wall + 0.01
        # Each phase holds at least its sleep.  How far a sleep overran
        # is the machine's load, not the ledger's: from above the phases
        # are held together, by the wall (`covered`, just checked).
        assert ph["disk"] >= 0.10
        assert ph["rpc_downstream"] >= 0.06
        assert ph["handler"] >= 0.06
        # The live phase sketches feed the labeled gauge.
        vals = server.slo.phase_gauge_values()
        assert ("phasetest", "/slowop", "disk", "0.99") in vals
        # ... and /debug/slo exposes them as JSON.
        snap = server.slo.snapshot()
        assert "disk" in snap["phases"]["/slowop"]
    finally:
        server.stop()


def test_queue_phase_measures_admission_wait():
    """A request that waited in the admission queue shows that wait as
    its `queue` phase — slow-because-queued must not read as
    slow-because-handler."""
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(1, queue_depth=4,
                                       queue_timeout=5.0))
    server.route("GET", "/work",
                 lambda q, b: (time.sleep(0.3), {"ok": True})[1])
    server.enable_metrics("queuetest")
    server.start()
    try:
        threads = [threading.Thread(
            target=lambda: rpc.call(
                f"http://127.0.0.1:{server.port}/work", timeout=10.0))
            for _ in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        queued = [e for e in server.slo.exemplars()
                  if e["phases"].get("queue", 0.0) > 0.2]
        assert queued, server.slo.exemplars()
        # Its handler time is still the real 0.3s, separately named.
        assert queued[0]["phases"]["handler"] >= 0.25
    finally:
        server.stop()


def test_phases_disabled_kill_switch(monkeypatch):
    monkeypatch.setattr(phases, "ENABLED", False)
    server = rpc.JsonHttpServer()
    server.route("GET", "/slowop",
                 lambda q, b: (time.sleep(0.3), {"ok": True})[1])
    server.enable_metrics("killtest")
    server.start()
    try:
        rpc.call(f"http://127.0.0.1:{server.port}/slowop")
        ex = server.slo.exemplars()
        assert ex and "phases" not in ex[0]
    finally:
        server.stop()


def test_phase_context_is_noop_without_ledger():
    """Instrumented code outside any request (background daemons,
    tests) pays one thread-local read and records nothing."""
    assert phases.active() is None
    with phases.phase("disk"):
        pass
    assert phases.active() is None


# -- lock-contention metering ------------------------------------------------

def test_contended_lock_records_wait_and_debug_locks_names_holder():
    lk = contention.MeteredLock("test.contended")

    def holder():
        with lk:
            time.sleep(0.25)

    th = threading.Thread(target=holder, name="holder-thread")
    th.start()
    time.sleep(0.05)

    def waiter():
        with lk:
            pass

    tw = threading.Thread(target=waiter, name="waiter-thread")
    tw.start()
    time.sleep(0.05)
    # While held + waited on: the snapshot names both, with stacks.
    snaps = [s for s in contention.snapshot_all()
             if s["lock"] == "test.contended"]
    assert snaps and snaps[0]["holder"]["thread"] == "holder-thread"
    assert any("holder" in line for line in
               snaps[0]["holder"]["stack"])
    assert any(w.get("thread") == "waiter-thread"
               for w in snaps[0]["waiters"])
    th.join()
    tw.join()
    # The contended wait landed in the histogram (~0.2s bucket range).
    text = "\n".join(contention.lock_wait_seconds.expose())
    assert 'lock="test.contended"' in text
    assert lk.contended >= 1
    assert contention.lock_wait_seconds.count(
        lock="test.contended") >= 1
    assert contention.lock_hold_seconds.count(
        lock="test.contended") >= 1


def test_contended_lock_wait_feeds_the_request_lock_phase():
    """A request blocked on a metered lock shows the wait as `lock` in
    its exemplar — the lock histogram and the phase budget agree."""
    lk = contention.MeteredLock("test.reqlock")
    server = rpc.JsonHttpServer()

    def locked_op(q, b):
        with lk:
            time.sleep(0.01)
        return {"ok": True}

    server.route("GET", "/locked", locked_op)
    server.enable_metrics("lockphase")
    server.start()
    release = threading.Event()

    def hog():
        with lk:
            release.wait(2.0)

    th = threading.Thread(target=hog)
    th.start()
    time.sleep(0.05)
    try:
        done = threading.Event()

        def call():
            rpc.call(f"http://127.0.0.1:{server.port}/locked",
                     timeout=10.0)
            done.set()

        tc = threading.Thread(target=call)
        tc.start()
        time.sleep(0.3)
        release.set()
        tc.join()
        assert done.is_set()
        ex = server.slo.exemplars()
        assert ex, "the lock-blocked request must exemplar"
        assert ex[0]["phases"]["lock"] >= 0.2
    finally:
        release.set()
        th.join()
        server.stop()


def test_disarmed_metered_lock_is_cheap(monkeypatch):
    """The fault-registry stance: disarmed metering must be one global
    check in front of the raw lock — bounded absolute overhead, no
    histogram traffic."""
    n = 20000
    raw = threading.Lock()
    t0 = time.perf_counter()
    for _ in range(n):
        with raw:
            pass
    raw_cycle = (time.perf_counter() - t0) / n

    monkeypatch.setattr(contention, "ENABLED", False)
    lk = contention.MeteredLock("test.disarmed")
    t0 = time.perf_counter()
    for _ in range(n):
        with lk:
            pass
    disarmed_cycle = (time.perf_counter() - t0) / n
    assert lk.acquired == 0          # no armed bookkeeping ran
    # Absolute bound (generous for CI): a couple of µs per cycle, and
    # nothing observed into the histograms.
    assert disarmed_cycle < max(20 * raw_cycle, 10e-6)
    assert contention.lock_wait_seconds.count(
        lock="test.disarmed") == 0
    assert contention.lock_hold_seconds.count(
        lock="test.disarmed") == 0


def test_armed_uncontended_fast_path_bounded(monkeypatch):
    """Armed but uncontended: try-acquire + holder bookkeeping + one
    hold observation — still microseconds, never a wait-histogram
    touch."""
    monkeypatch.setattr(contention, "ENABLED", True)
    lk = contention.MeteredLock("test.uncontended")
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        with lk:
            pass
    cycle = (time.perf_counter() - t0) / n
    assert cycle < 50e-6
    assert lk.acquired == n and lk.contended == 0
    # The wait histogram is never touched by uncontended acquires;
    # holds are observed (hold_observe_min defaults to 0).
    assert contention.lock_wait_seconds.count(
        lock="test.uncontended") == 0
    assert contention.lock_hold_seconds.count(
        lock="test.uncontended") == n


def test_metered_rlock_reentrancy():
    import threading as th
    lk = contention.MeteredLock("test.rlock", th.RLock())
    with lk:
        with lk:
            assert lk.locked()
    assert not lk.locked()
    # Hold measured outermost-to-outermost: exactly one observation.
    text = "\n".join(contention.lock_hold_seconds.expose())
    assert 'lock="test.rlock"' in text


# -- debug surfaces ----------------------------------------------------------

def _mk_stack(tmp_path):
    os.environ["SEAWEEDFS_TPU_PPROF"] = "1"
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.filer.server import FilerServer
    master = MasterServer(volume_size_limit_mb=64,
                          meta_dir=str(tmp_path))
    master.start()
    vs = VolumeServer(master.url(), [str(tmp_path / "vs")],
                      pulse_seconds=60)
    vs.start()
    filer = FilerServer(master.url())
    filer.start()
    return master, vs, filer


def test_debug_locks_and_promcheck_all_roles(tmp_path):
    """Live-scrape gate: /debug/locks answers on every role and every
    new instrument (phase gauge, lock histograms, runnable gauge)
    survives promcheck on master, volume server, and filer."""
    master, vs, filer = _mk_stack(tmp_path)
    try:
        import urllib.request
        # Traffic so phase sketches and lock holds have data.
        urllib.request.urlopen(urllib.request.Request(
            f"{filer.url()}/f.txt", data=b"x" * 2048, method="POST"),
            timeout=30).read()
        urllib.request.urlopen(f"{filer.url()}/f.txt",
                               timeout=30).read()
        for base in (master.url(), f"http://{vs.url()}"):
            locks = rpc.call(f"{base}/debug/locks")
            assert locks["metering"] is True
            names = {row["lock"] for row in locks["locks"]}
            assert "rpc.pool" in names  # client plane is shared
        # volume server saw a write -> its write lock is registered
        vs_locks = rpc.call(f"http://{vs.url()}/debug/locks")
        names = {row["lock"] for row in vs_locks["locks"]}
        assert "volume.write" in names
        scrapes = {
            "master": rpc.call(f"{master.url()}/metrics").decode(),
            "volume": rpc.call(f"http://{vs.url()}/metrics").decode(),
            "filer": filer.metrics_registry.expose(),
        }
        for role, text in scrapes.items():
            probs = validate_exposition(text)
            assert not probs, (role, probs[:5])
            assert "SeaweedFS_lock_wait_seconds" in text, role
            assert "SeaweedFS_lock_hold_seconds" in text, role
            assert "SeaweedFS_runnable_threads" in text, role
            assert "SeaweedFS_request_phase_seconds" in text, role
        # The volume server's scrape carries real hold samples for the
        # write path (value present, histogram well-formed per above).
        assert 'lock="volume.write"' in scrapes["volume"]
    finally:
        filer.stop()
        vs.stop()
        master.stop()
        os.environ.pop("SEAWEEDFS_TPU_PPROF", None)


def test_profile_window_serves_instantly_and_profile_is_exempt(
        tmp_path):
    """?window= answers from the always-on ring without sampling, and
    a profile of a saturated server is admission-exempt — profiling
    must work exactly when the lanes are full."""
    os.environ["SEAWEEDFS_TPU_PPROF_WINDOW"] = "0.3"
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(1, queue_depth=0,
                                       queue_timeout=0.1))
    os.environ["SEAWEEDFS_TPU_PPROF"] = "1"
    try:
        from seaweedfs_tpu.utils import pprof
        pprof.enable_pprof_routes(server)
        prof = pprof.ensure_continuous_profiler()
        release = threading.Event()
        server.route("GET", "/hog",
                     lambda q, b: (release.wait(10.0), {"ok": 1})[1])
        server.start()
        base = f"http://127.0.0.1:{server.port}"
        hog = threading.Thread(
            target=lambda: rpc.call(f"{base}/hog", timeout=30.0))
        hog.start()
        time.sleep(0.6)  # lane now occupied; ring has >= 1 window
        try:
            t0 = time.perf_counter()
            body = rpc.call(f"{base}/debug/pprof/profile?window=5")
            elapsed = time.perf_counter() - t0
            assert elapsed < 1.0, "ring reads must not sample"
            assert b"samples" in body
            assert prof.running
        finally:
            release.set()
            hog.join()
    finally:
        server.stop()
        os.environ.pop("SEAWEEDFS_TPU_PPROF", None)
        os.environ.pop("SEAWEEDFS_TPU_PPROF_WINDOW", None)


def test_runtime_attribution_toggle():
    """POST /debug/attribution?enabled=0|1 arms/disarms the whole
    plane restart-free — the overhead bench's A/B lever and the
    operator's rule-it-out switch."""
    server = rpc.JsonHttpServer()
    contention.setup_contention_routes(server)
    server.route("GET", "/slowop",
                 lambda q, b: (time.sleep(0.3), {"ok": True})[1])
    server.enable_metrics("toggletest")
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        out = rpc.call(f"{base}/debug/attribution?enabled=0", "POST")
        assert out["phases"] is False and out["lock_meter"] is False
        assert not phases.ENABLED and not contention.ENABLED
        rpc.call(f"{base}/slowop")
        assert "phases" not in server.slo.exemplars()[0]
        out = rpc.call(f"{base}/debug/attribution?enabled=1", "POST")
        assert out["phases"] is True and out["lock_meter"] is True
        rpc.call(f"{base}/slowop")
        assert "phases" in server.slo.exemplars()[0]
        locks = rpc.call(f"{base}/debug/locks")
        assert locks["metering"] is True
    finally:
        server.stop()
        contention.set_plane_enabled(True)


# -- cluster.profile ---------------------------------------------------------

def test_cluster_profile_merges_across_subprocess_cluster(tmp_path):
    """The acceptance shape: a real 3-node subprocess cluster (master
    + 2 volume servers), one cluster.profile, merged collapsed stacks
    with frames from >= 2 distinct nodes, written via -o."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SEAWEEDFS_TPU_PPROF="1",
               SEAWEEDFS_TPU_PPROF_WINDOW="1")
    procs = []
    mport = rpc.free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def spawn(args):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu"] + args, env=env,
            cwd=repo, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))

    spawn(["master", f"-port={mport}", f"-mdir={tmp_path}/meta"])
    vports = []
    for i in range(2):
        vport = rpc.free_port()
        os.makedirs(f"{tmp_path}/vs{i}")
        spawn(["volume", f"-port={vport}", f"-dir={tmp_path}/vs{i}",
               "-max=10", f"-mserver=127.0.0.1:{mport}"])
        vports.append(vport)
    try:
        deadline = time.time() + 180  # three interpreters beside 6 workers
        want = [f"http://127.0.0.1:{p}" for p in [mport] + vports]
        for url in want:
            while True:
                try:
                    rpc.call_status(f"{url}/debug/locks", timeout=2.0)
                    break
                except Exception:  # noqa: BLE001 — still starting
                    if time.time() > deadline:
                        raise TimeoutError(f"{url} never came up") \
                            from None
                    time.sleep(0.2)
        from seaweedfs_tpu.shell.command_profile import (
            ClusterProfile, merge_cluster_profile, parse_collapsed,
            strip_node_frames)
        merged, nodes = merge_cluster_profile(want, seconds=0.5)
        assert len(nodes) == 3
        prefixes = {s.split(";", 1)[0] for s in merged}
        assert len([p for p in prefixes if p.startswith("node:")]) >= 2
        # Through the shell command with -o, against the master env.
        from seaweedfs_tpu.shell.env import CommandEnv
        out_file = tmp_path / "cluster.collapsed"
        cenv = CommandEnv(f"http://127.0.0.1:{mport}")
        # The shell walks the nodes the MASTER lists: a volume server
        # that answers its own port may not have registered yet.
        deadline = time.time() + 120
        while len(cenv.debug_servers({})) < 3:
            assert time.time() < deadline, "volume servers never registered"
            time.sleep(0.2)
        text = ClusterProfile().do(
            ["-seconds", "0.5", "-o", str(out_file)], cenv)
        assert "node(s)" in text
        saved = parse_collapsed(out_file.read_text())
        assert saved, "collapsed output must round-trip"
        node_frames = {s.split(";", 1)[0] for s in saved}
        assert len([p for p in node_frames
                    if p.startswith("node:")]) >= 2
        # -diff against itself: near-zero movement, command succeeds.
        diff_text = ClusterProfile().do(
            ["-window", "2", "-diff", str(out_file)], cenv)
        assert "DELTA" in diff_text or "no stack-share" in diff_text
        assert strip_node_frames(saved)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
