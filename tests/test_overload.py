"""Overload protection & graceful lifecycle: admission-control lanes
(bounded queue, 429 + Retry-After sheds, internal-lane isolation), the
slow-loris idle-timeout reaper, disk-full safety (free-space reserve,
ENOSPC clean rollback, master steering), the drain lifecycle, and the
rolling-restart chaos acceptance test (SIGTERM-cycling subprocess
volume servers under sustained load with zero acknowledged-write loss
and zero client-visible errors)."""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from seaweedfs_tpu import fault
from seaweedfs_tpu.cluster import resilience, rpc
from seaweedfs_tpu.cluster.client import WeedClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.core import types as t
from seaweedfs_tpu.events import JOURNAL
from seaweedfs_tpu.stats.promcheck import validate_exposition
from seaweedfs_tpu.storage.volume import DiskFullError, Volume

pytestmark = pytest.mark.overload


# -- admission control: bounded queue + shed ---------------------------------

def test_burst_sheds_with_429_and_every_rejection_is_counted():
    """Acceptance: with the concurrency cap set low, a 10x burst gets
    bounded-queue behavior — shed requests receive 429 + Retry-After,
    admitted requests all succeed, and the shed counter accounts for
    every rejection."""
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(2, queue_depth=2,
                                       queue_timeout=5.0))
    server.route("GET", "/work",
                 lambda q, b: (time.sleep(0.15), {"ok": True})[1])
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    results: list = []
    lock = threading.Lock()

    def one():
        try:
            out = rpc.call(f"{base}/work", timeout=30.0)
            with lock:
                results.append(("ok", out))
        except rpc.RpcError as e:
            with lock:
                results.append(("shed", e))

    shed_before = rpc.requests_shed_total.value(lane="read")
    try:
        threads = [threading.Thread(target=one) for _ in range(20)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        server.stop()
    oks = [r for kind, r in results if kind == "ok"]
    sheds = [e for kind, e in results if kind == "shed"]
    assert len(oks) + len(sheds) == 20
    # 2 executing + 2 queued admitted at minimum; the rest shed.
    assert len(sheds) >= 10, f"only {len(sheds)} shed"
    assert all(out == {"ok": True} for out in oks)
    for e in sheds:
        assert e.status == 429
        assert e.retry_after == 1.0  # Retry-After rode the answer
    shed_delta = rpc.requests_shed_total.value(lane="read") - shed_before
    assert shed_delta == len(sheds), \
        f"counter {shed_delta} != rejections {len(sheds)}"


def test_internal_lane_cannot_starve_user_reads():
    """Priority isolation: internal traffic (X-Weed-Priority: low —
    replication, scrub repair, EC rebuilds) runs in its own smaller
    lane, so a repair storm saturating it sheds REPAIR traffic while
    user reads keep flowing untouched."""
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(4, queue_depth=0,
                                       queue_timeout=0.1))
    gate = threading.Event()
    server.route("GET", "/fetch",
                 lambda q, b: (gate.wait(5.0), {"ok": True})[1])
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    internal_results: list = []

    def internal():
        try:
            rpc.call(f"{base}/fetch", timeout=30.0,
                     headers=rpc.PRIORITY_LOW)
            internal_results.append(200)
        except rpc.RpcError as e:
            internal_results.append(e.status)

    try:
        # Storm the internal lane (cap = max(1, 4//4) = 1, queue 0).
        threads = [threading.Thread(target=internal) for _ in range(6)]
        for th in threads:
            th.start()
        time.sleep(0.3)  # one holds the slot on gate.wait; rest shed
        # User reads are untouched: their lane has free slots.
        t0 = time.perf_counter()
        gate.set()
        assert rpc.call(f"{base}/fetch", timeout=5.0) == {"ok": True}
        assert time.perf_counter() - t0 < 2.0
        for th in threads:
            th.join()
    finally:
        server.stop()
    assert 429 in internal_results, internal_results
    assert internal_results.count(200) >= 1


def test_exempt_paths_never_shed():
    """Introspection stays reachable exactly when the server is
    overloaded: /metrics (and healthz/debug) bypass admission."""
    server = rpc.JsonHttpServer(
        admission=rpc.AdmissionControl(1, queue_depth=0,
                                       queue_timeout=0.1))
    reg = server.enable_metrics("overloadtest")
    gate = threading.Event()
    server.route("GET", "/work",
                 lambda q, b: (gate.wait(5.0), {"ok": True})[1])
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    th = threading.Thread(
        target=lambda: rpc.call(f"{base}/work", timeout=30.0))
    try:
        th.start()
        time.sleep(0.2)  # the one slot is held
        # A second /work would shed — but /metrics must answer.
        text = bytes(rpc.call(f"{base}/metrics", timeout=5.0)).decode()
        assert "SeaweedFS_inflight_requests" in text
        assert not validate_exposition(text)
        row = next(ln for ln in text.splitlines()
                   if ln.startswith("SeaweedFS_inflight_requests")
                   and 'lane="read"' in ln)
        # The gated /work is visibly in flight.  The gauge is process-
        # global (it sums every live server's admission state), so
        # other suites' servers may contribute too: >= 1, not == 1.
        assert float(row.rsplit(" ", 1)[1]) >= 1
    finally:
        gate.set()
        th.join()
        server.stop()
    _ = reg


# -- slow-loris: idle timeout reaps stalled sockets --------------------------

def test_idle_timeout_reaps_slow_client_not_healthy_streams(
        monkeypatch):
    """Seeded net.slow_client fault: a client that stalls mid-request
    past the server's idle timeout is reaped (its socket dies), while
    a healthy request running concurrently on the same server is
    untouched."""
    monkeypatch.setenv("SEAWEEDFS_TPU_FAULTS_SEED", "7")
    server = rpc.JsonHttpServer(idle_timeout=1.0)
    server.route("GET", "/slowpath", lambda q, b: {"ok": True})
    server.route("GET", "/healthy", lambda q, b: {"ok": True})
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    healthy: list = []

    def healthy_loop():
        for _ in range(8):
            healthy.append(rpc.call(f"{base}/healthy", timeout=5.0))
            time.sleep(0.25)

    th = threading.Thread(target=healthy_loop)
    fault.arm("net.slow_client", "delay:2.5~/slowpath")
    try:
        th.start()
        with pytest.raises((ConnectionError, OSError)):
            rpc.call(f"{base}/slowpath", timeout=10.0)
    finally:
        fault.disarm_all()
        th.join()
        server.stop()
    assert len(healthy) == 8 and all(h == {"ok": True} for h in healthy)


def test_aio_reaps_stalled_faster_than_keepalive_idle():
    """Event-loop reap policy distinguishes two idle shapes: a conn
    with request bytes buffered but no progress (slow loris) dies at
    the HARD stall timeout, while an empty-buffer keep-alive conn — a
    healthy pooled client between requests — survives until the full
    -idle.timeout.  One timer for both would either kill every pooled
    client early or give sloris attackers the long budget."""
    import socket as socketlib
    server = rpc.JsonHttpServer(idle_timeout=4.0, stall_timeout=0.5,
                                transport="aio")
    server.route("GET", "/ping", lambda q, b: {"ok": True})
    server.start()
    try:
        addr = ("127.0.0.1", server.port)
        # Stalled mid-request: half a request line, then silence.
        stalled = socketlib.create_connection(addr, timeout=5.0)
        stalled.sendall(b"GET /pi")
        # Keep-alive idle: one complete request, then silence.
        idle = socketlib.create_connection(addr, timeout=5.0)
        idle.sendall(b"GET /ping HTTP/1.1\r\nHost: x\r\n\r\n")
        assert b"200" in idle.recv(4096)
        deadline = time.time() + 3.0
        reaped = None
        while time.time() < deadline:
            stalled.settimeout(0.25)
            try:
                if stalled.recv(1) == b"":
                    reaped = time.time()
                    break
            except TimeoutError:
                continue
            except OSError:
                reaped = time.time()
                break
        assert reaped is not None, \
            "stalled conn survived well past stall_timeout"
        # The idle keep-alive conn must still be usable afterwards...
        idle.settimeout(5.0)
        idle.sendall(b"GET /ping HTTP/1.1\r\nHost: x\r\n\r\n")
        assert b"200" in idle.recv(4096)
        # ...and the registry recorded the reap with the right kind.
        snap = rpc.call(f"http://127.0.0.1:{server.port}/debug/conns")
        assert snap["transport"] == "aio"
        from seaweedfs_tpu.netcore.registry import conns_reaped_total
        assert conns_reaped_total.value(kind="stalled") >= 1
        idle.close()
        stalled.close()
    finally:
        server.stop()


# -- disk-full safety ---------------------------------------------------------

def test_enospc_rolls_back_cleanly_no_torn_tail(tmp_path):
    """Acceptance: an ENOSPC mid-append (injected: half the record
    lands) is rolled back in place — the .dat keeps no torn tail, the
    volume flips readonly, and a remount needs NO crash recovery and
    serves every previously-acked needle."""
    from seaweedfs_tpu.core.needle import Needle
    v = Volume(str(tmp_path), "", 7, use_worker=False)
    v.write_needle(Needle(cookie=1, id=1, data=b"first " * 64))
    size_before = v.dat_size()
    fault.arm("disk.full", "fail*1")
    try:
        with pytest.raises(DiskFullError):
            v.write_needle(Needle(cookie=1, id=2, data=b"boom " * 64))
    finally:
        fault.disarm_all()
    assert v.readonly
    assert v.dat_size() == size_before          # partial record gone
    assert os.path.getsize(v.file_name() + ".dat") == size_before
    assert v.dat_size() % t.NEEDLE_PADDING_SIZE == 0
    v.close()

    recovered_before = sum(
        1 for e in JOURNAL.snapshot(type_="volume.recovered"))
    v2 = Volume(str(tmp_path), "", 7, create=False, use_worker=False)
    # Remount: clean (no volume.recovered emitted — nothing to heal),
    # the acked needle is intact, and the volume writes again.
    recovered_after = sum(
        1 for e in JOURNAL.snapshot(type_="volume.recovered"))
    assert recovered_after == recovered_before, \
        "ENOSPC rollback left work for crash recovery"
    assert v2.read_needle(1).data == b"first " * 64
    v2.write_needle(Needle(cookie=1, id=3, data=b"after enospc"))
    assert v2.read_needle(3).data == b"after enospc"
    v2.close()


def test_disk_reserve_flips_readonly_and_master_steers(tmp_path):
    """Acceptance: a breached free-space reserve flips the node's
    volumes readonly BEFORE ENOSPC, the heartbeat carries the low-disk
    flag, /cluster/healthz reports it, the reserve-breached gauge
    scrapes, and the master's assignment steers to healthy nodes —
    recovering once the reserve is satisfied again."""
    master = MasterServer(pulse_seconds=60)
    master.start()
    servers = []
    try:
        for i in range(2):
            d = tmp_path / f"vs{i}"
            d.mkdir()
            vs = VolumeServer(master.url(), [str(d)],
                              max_volume_counts=[50], pulse_seconds=60)
            vs.start()
            servers.append(vs)
        client = WeedClient(master.url())
        fid = client.upload_data(b"pre-breach payload")
        low = servers[0]

        # Breach: an absurd reserve no disk satisfies.
        low.store.disk_reserve_bytes = 1 << 60
        low._send_heartbeat(full=True)
        assert low.store.low_disk_dirs
        assert all(v.readonly for loc in low.store.locations
                   for v in loc.volumes.values())
        status, doc = rpc.call_status(
            f"{master.url()}/cluster/healthz")
        assert status == 503
        assert any("disk reserve breached" in p
                   for p in doc["problems"]), doc["problems"]
        row = next(n for n in doc["nodes"] if n["node"] == low.url())
        assert row["low_disk"]
        scrape = bytes(rpc.call(f"http://{low.url()}/metrics")).decode()
        assert not validate_exposition(scrape)
        breached = [ln for ln in scrape.splitlines()
                    if ln.startswith("SeaweedFS_disk_reserve_breached")]
        assert breached and breached[0].endswith(" 1")

        # Steering: every new assignment lands on the healthy node.
        for _ in range(8):
            a = rpc.call(f"{master.url()}/dir/assign")
            assert a["url"] == servers[1].url(), a
        # Uploads still succeed (they ride the steering).
        assert client.upload_data(b"written during breach")
        # Reads of pre-breach data still serve (readonly, not gone).
        assert client.download(fid) == b"pre-breach payload"

        # Recovery: reserve satisfied again -> flips back, healthz 200.
        # The recovery itself must force a full heartbeat (the flip
        # list is non-empty in BOTH directions), or the master would
        # keep the recovered volumes out of its writable pool forever.
        low.store.disk_reserve_bytes = 1
        low._send_heartbeat()  # a DELTA beat: recovery must upgrade it
        assert not low.store.low_disk_dirs
        assert not any(v.readonly for loc in low.store.locations
                       for v in loc.volumes.values())
        status, doc = rpc.call_status(
            f"{master.url()}/cluster/healthz")
        assert status == 200, doc["problems"]
        # ...and the master assigns to the recovered node again.  The
        # pick among writable volumes is random, so sample until the
        # recovered node shows up (a fixed 20-draw sample can miss a
        # minority holder on a slow 1-core host).
        deadline = time.monotonic() + 10
        seen = set()
        while low.url() not in seen and time.monotonic() < deadline:
            seen.add(rpc.call(f"{master.url()}/dir/assign")["url"])
        assert low.url() in seen, seen
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


def test_enospc_on_live_server_steers_and_client_recovers(tmp_path):
    """End-to-end ENOSPC: the write 500s (rolled back server-side),
    the client's re-assign machinery lands the retry on a healthy
    volume, and the poisoned volume never serves a torn byte."""
    master = MasterServer(pulse_seconds=60)
    master.start()
    servers = []
    try:
        for i in range(2):
            d = tmp_path / f"vs{i}"
            d.mkdir()
            vs = VolumeServer(master.url(), [str(d)],
                              max_volume_counts=[50], pulse_seconds=60)
            vs.start()
            servers.append(vs)
        client = WeedClient(master.url())
        client.upload_data(b"warmup")  # grows the layout
        fault.arm("disk.full", "fail*1")
        try:
            fid = client.upload_data(b"survives enospc " * 16)
        finally:
            fault.disarm_all()
        # The retry (fresh assign) succeeded and reads back intact.
        assert client.download(fid) == b"survives enospc " * 16
        assert any(e["type"] == "disk.full"
                   for e in JOURNAL.snapshot(type_="disk.full"))
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


# -- graceful lifecycle -------------------------------------------------------

def test_drain_refuses_new_writes_finishes_inflight(tmp_path):
    """Draining: new writes get 503 + Retry-After while an in-flight
    request admitted BEFORE the drain completes normally; the goodbye
    unregisters the node with no dead-sweep window and the shell's
    cluster.drain drives the whole flow."""
    from seaweedfs_tpu.shell import CommandEnv, run_command
    master = MasterServer(pulse_seconds=60)
    master.start()
    vs = None
    slow_result: list = []
    try:
        d = tmp_path / "vs"
        d.mkdir()
        vs = VolumeServer(master.url(), [str(d)],
                          max_volume_counts=[50], pulse_seconds=60)
        vs.start()
        client = WeedClient(master.url())
        fid = client.upload_data(b"pre-drain")
        vid = t.parse_file_id(fid)[0]

        # An in-flight request admitted BEFORE the drain (a gated slow
        # handler on the real server) must complete: the drain waits
        # for the admission controller's in-flight count to hit zero.
        gate = threading.Event()
        entered = threading.Event()
        vs.server.route("GET", "/slowop", lambda q, b: (
            entered.set(), gate.wait(10.0), {"done": True})[2])

        def slow_call():
            try:
                slow_result.append(
                    rpc.call(f"http://{vs.url()}/slowop",
                             timeout=30.0))
            except Exception as e:  # noqa: BLE001
                slow_result.append(e)

        th = threading.Thread(target=slow_call)
        th.start()
        assert entered.wait(10.0)
        # Release the gate shortly after the drain begins waiting.
        threading.Timer(0.5, gate.set).start()

        env = CommandEnv(master.url())
        t0 = time.monotonic()
        try:
            out = run_command(env, f"cluster.drain -node {vs.url()} "
                                   f"-grace 15")
        finally:
            env.close()
        assert "drained" in out
        # The drain waited for the in-flight request (released at
        # ~0.5s) instead of cutting it off or burning the full grace.
        assert 0.3 <= time.monotonic() - t0 < 10.0
        th.join(timeout=10)
        assert slow_result == [{"done": True}], \
            f"in-flight request failed: {slow_result}"

        # New writes: 503 + Retry-After with a draining message.
        with pytest.raises(rpc.RpcError) as ei:
            rpc.call(f"http://{vs.url()}/{vid},1f00000001", "POST",
                     b"refused")
        assert ei.value.status == 503
        assert "draining" in ei.value.message
        assert ei.value.retry_after is not None

        # The master unregistered the node instantly — and healthz
        # never calls it heartbeat-lost.
        assert all(dn.url() != vs.url()
                   for dn in master.topo.leaves())
        status, doc = rpc.call_status(f"{master.url()}/cluster/healthz")
        assert not any("heartbeat stale" in p
                       for p in doc.get("problems", []))
        # Reads keep being served until the process actually exits.
        assert bytes(rpc.call(f"http://{vs.url()}/{fid}")) \
            == b"pre-drain"
        # Drain events are on the timeline.
        assert JOURNAL.snapshot(type_="node.draining")
        assert JOURNAL.snapshot(type_="node.drained")
    finally:
        if vs is not None:
            vs.stop()
        master.stop()


def test_a_sigterm_that_lands_on_another_thread_still_stops_the_server():
    """The kernel may hand a process-directed signal to any thread.
    CPython then runs the handler in the main thread the next time it
    executes bytecode — never, if it sits in an untimed lock acquire.
    `_wait_forever` must come back (drain, stop) all the same: this is
    what left a `volume` process running a minute after its SIGTERM in
    the rolling restart below."""
    from seaweedfs_tpu.command import servers

    assert threading.current_thread() is threading.main_thread()
    main = threading.main_thread().ident
    stopped = []
    rescued = []
    parked = threading.Event()

    def bystander() -> None:
        parked.wait(30)

    def sender() -> None:
        time.sleep(0.2)     # the main thread is in its wait by now
        signal.pthread_kill(other.ident, signal.SIGTERM)
        time.sleep(5.0)
        if not stopped:
            # Unrepaired, only a signal to the main thread itself ends
            # the wait: end it, and fail below.
            rescued.append(True)
            signal.pthread_kill(main, signal.SIGTERM)

    class Role:
        def stop(self) -> None:
            stopped.append(True)

    other = threading.Thread(target=bystander, daemon=True)
    other.start()
    saved = {s: signal.getsignal(s)
             for s in (signal.SIGINT, signal.SIGTERM)}
    threading.Thread(target=sender, daemon=True).start()
    try:
        assert servers._wait_forever([Role()]) == 0
    finally:
        parked.set()
        for s, h in saved.items():
            signal.signal(s, h)
    assert stopped and not rescued


def _spawn_volume_subprocess(tmp_path, idx: int, port: int,
                             master_port: int):
    d = tmp_path / f"vsdata{idx}"
    d.mkdir(exist_ok=True)
    # Append (not truncate) the per-node log across restarts, and pin
    # the child to the CPU backend regardless of the parent's env — a
    # subprocess dialing real accelerator plumbing would hang past the
    # registration deadline.
    log = open(tmp_path / f"vs{idx}.log", "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "volume",
         f"-port={port}", f"-dir={d}", "-max=50",
         f"-mserver=127.0.0.1:{master_port}",
         "-shutdown.grace=10"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONFAULTHANDLER="1"),
        stdout=log, stderr=subprocess.STDOUT)


def _dead_subprocess_report(tmp_path, procs) -> str | None:
    for i, proc in procs.items():
        if proc.poll() is not None:
            try:
                tail = (tmp_path / f"vs{i}.log").read_bytes()[-2000:]
            except OSError:
                tail = b""
            return (f"volume subprocess {i} died rc={proc.returncode}:"
                    f" {tail.decode(errors='replace')}")
    return None


def test_rolling_restart_zero_acked_loss_zero_client_errors(tmp_path):
    """Acceptance: SIGTERM-cycling every subprocess volume server in
    turn under a continuous upload/read burst yields zero
    acknowledged-write loss and zero client-visible errors (after
    RetryPolicy failover), with the drain visible in the event journal
    and /cluster/healthz never reporting a drained node as
    heartbeat-lost."""
    master = MasterServer(volume_size_limit_mb=64,
                          meta_dir=str(tmp_path / "meta"),
                          pulse_seconds=2)
    master.start()
    # free_port() can hand back duplicates (bind-close races): the
    # three servers need three DISTINCT ports or one dies at bind.
    ports: list[int] = []
    while len(ports) < 3:
        p = rpc.free_port()
        if p not in ports and p != master.server.port:
            ports.append(p)
    procs = {}
    client_errors: list = []
    healthz_violations: list = []
    acked: list[tuple[str, bytes]] = []
    lock = threading.Lock()
    stop = threading.Event()
    try:
        for i, port in enumerate(ports):
            procs[i] = _spawn_volume_subprocess(
                tmp_path, i, port, master.server.port)
        deadline = time.time() + 120
        while len(list(master.topo.leaves())) < 3:
            dead = _dead_subprocess_report(tmp_path, procs)
            if dead:
                raise RuntimeError(dead)
            if time.time() > deadline:
                raise TimeoutError("subprocess servers never registered")
            time.sleep(0.2)

        client = WeedClient(
            master.url(),
            retry_policy=resilience.RetryPolicy(
                max_attempts=8, base_delay=0.05, max_delay=0.5,
                per_attempt_timeout=10.0, total_deadline=30.0))

        def writer(k: int) -> None:
            i = 0
            while not stop.is_set():
                payload = f"rolling {k}-{i} ".encode() * 16
                try:
                    out = client.upload(payload, replication="001")
                except Exception as e:  # noqa: BLE001
                    with lock:
                        client_errors.append(f"upload: {e}")
                    continue
                with lock:
                    acked.append((out["fid"], payload))
                i += 1
                time.sleep(0.01)

        def reader() -> None:
            while not stop.is_set():
                with lock:
                    sample = acked[-20:]
                for fid, payload in sample:
                    try:
                        if client.download(fid) != payload:
                            with lock:
                                client_errors.append(
                                    f"read {fid}: bytes differ")
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            client_errors.append(f"read {fid}: {e}")
                time.sleep(0.05)

        def healthz_watch() -> None:
            while not stop.is_set():
                try:
                    _st, doc = rpc.call_status(
                        f"{master.url()}/cluster/healthz", timeout=5.0)
                    for p in doc.get("problems", []):
                        if "heartbeat stale" in p:
                            healthz_violations.append(p)
                except Exception:  # noqa: BLE001
                    pass
                time.sleep(0.3)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(3)]
        threads.append(threading.Thread(target=reader))
        threads.append(threading.Thread(target=healthz_watch))
        for th in threads:
            th.start()

        # Let the burst get going.
        deadline = time.time() + 60
        while len(acked) < 30 and time.time() < deadline:
            time.sleep(0.1)
        assert len(acked) >= 30, "burst never got going"

        # Roll every server: SIGTERM (graceful drain) -> wait exit ->
        # restart -> wait re-register.
        for i, port in enumerate(ports):
            proc = procs[i]
            os.kill(proc.pid, signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                # Say what kept it: SIGABRT makes the child's
                # faulthandler write every thread's stack to its log.
                os.kill(proc.pid, signal.SIGABRT)
                proc.wait(timeout=30)
                tail = (tmp_path / f"vs{i}.log").read_bytes()[-6000:]
                raise AssertionError(
                    f"volume subprocess {i} outlived its SIGTERM by "
                    f"60 s:\n{tail.decode(errors='replace')}") from None
            procs[i] = _spawn_volume_subprocess(
                tmp_path, i, port, master.server.port)
            node = f"127.0.0.1:{port}"
            deadline = time.time() + 120
            while all(dn.url() != node
                      for dn in master.topo.leaves()):
                dead = _dead_subprocess_report(tmp_path, {i: procs[i]})
                if dead:
                    raise RuntimeError(dead)
                if time.time() > deadline:
                    raise TimeoutError(f"{node} never re-registered")
                time.sleep(0.2)
            # Keep load flowing a moment between cycles.
            time.sleep(0.5)

        stop.set()
        for th in threads:
            th.join(timeout=60)

        assert not client_errors, \
            f"{len(client_errors)} client-visible errors: " \
            f"{client_errors[:5]}"
        assert not healthz_violations, healthz_violations[:5]
        # Drain visible on the timeline: one node.drained per SIGTERM.
        assert len(JOURNAL.snapshot(type_="node.drained")) >= 3

        # Zero acknowledged-write loss: every acked fid reads back.
        lost = []
        for fid, payload in acked:
            try:
                if client.download(fid) != payload:
                    lost.append((fid, "bytes differ"))
            except Exception as e:  # noqa: BLE001
                lost.append((fid, str(e)))
        assert not lost, \
            f"{len(lost)}/{len(acked)} acked writes lost: {lost[:5]}"
    finally:
        stop.set()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        master.stop()


# -- live-scrape: the new instruments ----------------------------------------

def test_new_overload_gauges_scrape_clean(tmp_path, monkeypatch):
    """promcheck-gated live scrape: the shed counter, in-flight gauge,
    and reserve-breached gauge all expose on a real volume server and
    parse clean under the promtool-style validator; fault.ls lists the
    two new fault points."""
    monkeypatch.setenv("SEAWEEDFS_TPU_FAULTS_DEBUG", "1")
    master = MasterServer(pulse_seconds=60)
    master.start()
    vs = None
    try:
        d = tmp_path / "vs"
        d.mkdir()
        vs = VolumeServer(master.url(), [str(d)],
                          max_volume_counts=[10], pulse_seconds=60,
                          max_concurrent=1, queue_depth=0)
        vs.start()
        # Force one shed so the labeled counter has a sample.
        gate = threading.Event()
        held = threading.Thread(target=lambda: rpc.call(
            f"http://{vs.url()}/ui", timeout=30.0))
        vs.server.route("GET", "/ui", lambda q, b: (
            gate.wait(5.0), (200, b"", {}))[1])
        held.start()
        time.sleep(0.2)
        with pytest.raises(rpc.RpcError) as ei:
            rpc.call(f"http://{vs.url()}/ui", timeout=5.0)
        assert ei.value.status == 429
        gate.set()
        held.join()
        scrape = bytes(rpc.call(f"http://{vs.url()}/metrics")).decode()
        assert not validate_exposition(scrape), \
            validate_exposition(scrape)[:3]
        for name in ("SeaweedFS_requests_shed_total",
                     "SeaweedFS_inflight_requests",
                     "SeaweedFS_disk_reserve_breached"):
            assert name in scrape, f"{name} missing from scrape"
        # fault.ls lists the new points.
        from seaweedfs_tpu.shell import CommandEnv, run_command
        env = CommandEnv(master.url())
        try:
            out = run_command(env, "fault.ls")
        finally:
            env.close()
        assert "disk.full" in out and "net.slow_client" in out
    finally:
        if vs is not None:
            vs.stop()
        master.stop()
