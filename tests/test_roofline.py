"""Device kernel ledger (stats/roofline.py + the streamed-pipeline
occupancy recorder).

Covers: the kernel catalog is closed and anti-rot tested, the analytic
cost model matches the Pallas CostEstimate algebra exactly, kernel
rows land in a bounded ring with absolute totals, the conservation
check pins analytic bytes to ledger-measured bytes within max(1%,
4KB), /debug/device serves the rows the benchmark reads and no share
of a roofline, answering it compiles and transfers nothing,
PipelineRecorder survives
production duty (bounded overflow, concurrent writers, exact
injected-clock gantt/occupancy/bubble math), sustained occupancy
collapse emits a rate-limited device.slow event, the disarmed path is
a single flag check (the record hook is provably never reached), a
deliberately slow fence is included in the reported kernel wall
(execution-fencing regression), nbytes=0 observations still
materialize the ec_stage_bytes series, and the four new instruments
scrape promcheck-clean on master and volume server of a live cluster
with /debug/device, /cluster/device, healthz, and cluster.roofline
all agreeing."""

import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.cluster import rpc
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.events.journal import JOURNAL
from seaweedfs_tpu.ops import coder_pallas, crc_fold
from seaweedfs_tpu.ops.coder_pallas import PallasCoder
from seaweedfs_tpu.parallel.stream_pipeline import PipelineRecorder
from seaweedfs_tpu.shell import CommandEnv, run_command
from seaweedfs_tpu.stats import metrics, roofline
from seaweedfs_tpu.stats.promcheck import validate_exposition

pytestmark = pytest.mark.roofline


# -- catalog + cost model ----------------------------------------------------

def test_kernel_catalog_anti_rot():
    """Closed catalog, like events TYPES and flows PURPOSES: exactly
    the documented kernels exist, each validates and has a
    description; anything else raises at the record site."""
    expected = {"encode_kernel", "encode_crc_kernel",
                "reconstruct_kernel", "batch_encode",
                "batch_reconstruct"}
    assert set(roofline.KERNELS) == expected
    for k in roofline.KERNELS:
        assert roofline.validate(k) == k
        assert roofline.KERNELS[k], f"kernel {k} has no description"
    for bad in ("encode", "", "ENCODE_KERNEL", "matmul"):
        with pytest.raises(ValueError):
            roofline.validate(bad)
    ledger = roofline.RooflineLedger()
    with pytest.raises(ValueError):
        ledger.record("matmul", "rs", "int8", out_rows=4, in_rows=10,
                      n=64, seconds=0.1)
    assert roofline.PIPELINE_STAGES == ("stack", "dispatch", "device",
                                        "drain")


def test_cost_model_algebra():
    """The analytic model IS the Pallas CostEstimate algebra: bytes =
    (in+out)*n, macs = 8*out * 8*in * n, CRC folds 8*(in+out)*32*n
    more, flops = 2*macs, everything linear in batch."""
    c = roofline.cost_model(4, 10, 4096)
    assert c["bytes"] == 14 * 4096
    assert c["macs"] == 8 * 4 * 8 * 10 * 4096
    assert c["flops"] == 2 * c["macs"]
    assert c["intensity"] == pytest.approx(c["flops"] / c["bytes"])

    crc = roofline.cost_model(4, 10, 4096, crc=True)
    assert crc["bytes"] == c["bytes"]
    assert crc["macs"] == c["macs"] + 8 * 14 * 32 * 4096

    b = roofline.cost_model(4, 10, 4096, batch=3)
    assert b["bytes"] == 3 * c["bytes"]
    assert b["macs"] == 3 * c["macs"]

    assert roofline.geometry_key(4, 10, 4096) == "4x10x4096"
    assert roofline.geometry_key(4, 10, 4096, batch=8) == "4x10x4096b8"


# -- the ledger --------------------------------------------------------------

def test_ledger_ring_bounded_and_conservation():
    """300 records: the ring holds the newest 256, the series totals
    stay absolute (heartbeat merge is idempotent), and conservation
    flags exactly the row whose measured bytes drifted past
    max(1%, 4KB)."""
    t = [1000.0]
    ledger = roofline.RooflineLedger(clock=lambda: t[0])
    cost = roofline.cost_model(4, 10, 4096)
    for _ in range(300):
        t[0] += 0.01
        row = ledger.record(
            "encode_kernel", "rs", "int8", out_rows=4, in_rows=10,
            n=4096, seconds=0.002, measured_bytes=cost["bytes"])
    assert row["geometry"] == "4x10x4096"
    assert len(ledger.recent(1000)) == roofline._RING_MAX

    table = ledger.kernel_table()
    assert len(table) == 1
    assert table[0]["count"] == 300
    assert table[0]["seconds"] == pytest.approx(300 * 0.002, rel=1e-3)
    assert table[0]["bytes"] == 300 * cost["bytes"]
    assert table[0]["work"] == 300 * cost["macs"]

    cons = ledger.conservation()
    assert cons["ok"] and cons["checked"] == roofline._RING_MAX

    # Off-by-more-than-tolerance measured bytes: the model drifted.
    ledger.record("encode_kernel", "rs", "int8", out_rows=4,
                  in_rows=10, n=4096, seconds=0.1,
                  measured_bytes=cost["bytes"] * 2)
    cons = ledger.conservation()
    assert not cons["ok"]
    assert cons["violations"][0]["kernel"] == "encode_kernel"


def test_real_encode_records_and_conserves():
    """The PallasCoder call sites feed the process ledger with
    measured bytes equal to the analytic payload — conservation by
    construction, checked against a real (interpret-mode) encode,
    fused-CRC encode, and reconstruct."""
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        pc = PallasCoder(4, 2)
        data = np.arange(4 * 2048, dtype=np.uint8).reshape(4, 2048)
        parity = np.asarray(pc.encode(data))
        assert parity.shape == (2, 2048)
        # the fused kernel takes whole `.ecc` blocks only
        pc.encode_with_crc(np.resize(data, (4, crc_fold.BLOCK)))
        shards = {i: data[i] for i in range(4)}
        shards[4] = parity[0]
        pc.reconstruct({k: v for k, v in shards.items() if k != 0},
                       wanted=[0])
        kinds = {r["kernel"] for r in roofline.LEDGER.recent()}
        assert {"encode_kernel", "encode_crc_kernel",
                "reconstruct_kernel"} <= kinds
        cons = roofline.LEDGER.conservation()
        assert cons["ok"], cons["violations"]
        assert cons["checked"] >= 3
    finally:
        roofline.LEDGER.reset()


@pytest.mark.parametrize("fused,kernel", [
    pytest.param("1", "encode_crc_kernel", id="fused_crc"),
    pytest.param("0", "encode_kernel", id="byte_accumulators"),
])
def test_a_seal_records_no_kernel_row_and_a_direct_call_does(
        monkeypatch, tmp_path, fused, kernel):
    """The ledger is fed fenced walls only: the seal's pipeline, which
    drains later, asks the coder for the unfenced call and leaves no
    `encode*` row (a dispatch-only wall would read as an impossible
    rate), yet the process is known to drive a device; a direct call
    still fences and records.  The sealed files are the CPU path's."""
    from seaweedfs_tpu.ec import SMALL_BLOCK_SIZE as BLOCK, to_ext
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.integrity import ShardChecksums
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder

    monkeypatch.setenv("SEAWEEDFS_TPU_EC_FUSED_CRC", fused)
    monkeypatch.setattr(encoder, "SEAL_INFLIGHT", encoder._InflightCount())
    blob = np.random.default_rng(3).integers(
        0, 256, 2 * 10 * BLOCK - 4321, dtype=np.uint8).tobytes()
    bases = [str(tmp_path / name) for name in ("pallas", "numpy")]
    for base in bases:
        with open(base + ".dat", "wb") as f:
            f.write(blob)
    pc = PallasCoder()
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        encoder.write_ec_files(bases[0], coder=pc, chunk_size=BLOCK)
        assert not roofline.LEDGER.recent()
        assert not roofline.LEDGER.kernel_table()
        assert roofline.LEDGER.has_rows()
        doc = roofline.debug_doc("n:1", "volume")
        assert doc["devices"]
        got = doc["seal_inflight"]
        assert got["ready"] + got["waited"] == 2
        rows = {r["kernel"]: r["count"] for r in doc["kernels"]}
        assert rows["seal.dispatch"] == rows["seal.drain"] == 2
        data = np.zeros((10, BLOCK), np.uint8)
        if fused == "1":
            pc.encode_with_crc(data)
        else:
            pc.encode(data)
        assert [r["kernel"] for r in roofline.LEDGER.recent()] == [kernel]
    finally:
        roofline.LEDGER.reset()
    encoder.write_ec_files(bases[1], coder=NumpyCoder(), chunk_size=BLOCK)
    ecc = [ShardChecksums.load(base) for base in bases]
    for sid in range(14):
        with open(bases[0] + to_ext(sid), "rb") as a, \
                open(bases[1] + to_ext(sid), "rb") as b:
            assert a.read() == b.read(), sid
        assert ecc[0].get(sid) == ecc[1].get(sid), sid


def test_disarmed_path_is_one_flag_check(monkeypatch):
    """-roofline=false reduces every call site to the ARMED check: a
    booby-trapped record hook proves the accounting code is never
    reached, and the kernels still run."""
    def boom(*a, **k):
        raise AssertionError("roofline hook reached while disarmed")

    monkeypatch.setattr(coder_pallas, "_record_roofline", boom)
    monkeypatch.setattr(roofline.RooflineLedger, "record", boom)
    roofline.set_armed(False)
    try:
        pc = PallasCoder(4, 2)
        data = np.ones((4, 1024), np.uint8)
        out = np.asarray(pc.encode(data))
        assert out.shape == (2, 1024)
        pc.encode_with_crc(np.ones((4, crc_fold.BLOCK), np.uint8))
    finally:
        roofline.set_armed(True)


def test_debug_device_rows_carry_what_the_benchmark_reads_and_no_share_of_a_roofline():
    """`benchmark/served.py` `coder_rows()` takes `kernel`, `count`,
    `seconds`, `bytes` from every row of `/debug/device`'s `kernels`
    list, kernel rows and stage rows alike.  The program serves no
    share of a roofline beside them: that number is the benchmark's,
    from the device trace."""
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        PallasCoder(4, 2).encode(np.ones((4, 2048), np.uint8))
        with roofline.StageClock("rs")("seal.drain", 7):
            pass
        doc = roofline.debug_doc("n:1", "volume")
        rows = doc["kernels"]
        assert {r["kernel"] for r in rows} == {"encode_kernel",
                                               "seal.drain"}
        for r in rows:
            assert {"kernel", "count", "seconds", "bytes"} <= set(r), r
            assert r["count"] == 1 and r["seconds"] > 0 and r["bytes"] > 0
            assert not [k for k in r if k.startswith("achieved")], r
        for r in doc["recent"]:
            assert not [k for k in r if k.startswith("achieved")], r
        assert "peaks" not in doc
    finally:
        roofline.LEDGER.reset()


def test_answering_debug_device_compiles_and_transfers_nothing(
        monkeypatch):
    """The first `/debug/device` a process answers, and every fenced
    call it records, is bookkeeping: no probe compiles a program or
    moves bytes on the serving process's chip."""
    import jax

    reached = []

    def boom(*a, **k):
        reached.append(a)   # a caller may swallow the error: count too
        raise AssertionError("device work while answering /debug/device")

    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        roofline.LEDGER.mark_device()
        monkeypatch.setattr(jax, "jit", boom)
        monkeypatch.setattr(jax, "device_put", boom)
        row = roofline.LEDGER.record(
            "reconstruct_kernel", "rs", "int8", out_rows=2, in_rows=10,
            n=4096, seconds=0.001, measured_bytes=12 * 4096)
        assert row["kernel"] == "reconstruct_kernel"
        doc = roofline.debug_doc("n:1", "volume")
        assert doc["devices"]
        assert [r["count"] for r in doc["kernels"]] == [1]
        assert not reached
    finally:
        roofline.LEDGER.reset()


def test_fencing_includes_device_wait(monkeypatch):
    """Execution-fencing regression: when the fence itself takes 50ms
    (modeling in-flight device work at block_until_ready time), the
    recorded kernel wall must include it.  A timer stopped before the
    fence — the async-dispatch flattery bug — fails here."""
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    real_fence = coder_pallas.jax.block_until_ready

    def slow_fence(x):
        time.sleep(0.05)
        return real_fence(x)

    monkeypatch.setattr(coder_pallas.jax, "block_until_ready",
                        slow_fence)
    try:
        PallasCoder(4, 2).encode(np.ones((4, 1024), np.uint8))
        rows = [r for r in roofline.LEDGER.recent()
                if r["kernel"] == "encode_kernel"]
        assert rows, "encode never recorded"
        assert rows[-1]["seconds"] >= 0.05
    finally:
        roofline.LEDGER.reset()


def test_observe_ec_stage_counts_zero_bytes():
    """Satellite fix: nbytes=0 observations must still materialize the
    stage's ec_stage_bytes series (a family that only appears under
    byte-carrying load reads as a counter reset in rate() and silently
    under-counts stages whose first calls are zero-byte)."""
    stage = "zb_regression_stage"
    text0 = "\n".join(metrics.ec_stage_bytes.expose())
    assert f'stage="{stage}"' not in text0
    metrics.observe_ec_stage(stage, 0.001, 0)
    text1 = "\n".join(metrics.ec_stage_bytes.expose())
    assert f'stage="{stage}"' in text1
    assert metrics.ec_stage_bytes.value(stage=stage) == 0.0
    metrics.observe_ec_stage(stage, 0.001, 7)
    assert metrics.ec_stage_bytes.value(stage=stage) == 7.0


# -- PipelineRecorder as production component --------------------------------

def test_recorder_bounded_overflow():
    """Production duty means constant memory: both the event and span
    rings drop the oldest entries past maxlen, and the read side keeps
    computing over whatever survived."""
    rec = PipelineRecorder(maxlen=8)
    for i in range(100):
        rec.record("dispatched", i)
        rec.note_span("device", i, float(i), float(i) + 0.5)
    assert len(rec.events()) == 8
    assert len(rec.spans()) == 8
    assert [s[1] for s in rec.spans()] == list(range(92, 100))
    occ = rec.device_occupancy()
    assert occ["fraction"] is not None
    assert rec.gantt(last=4)[-1]["index"] == 99


def test_recorder_concurrent_writers():
    """Stages run on pool threads plus the main drain loop; concurrent
    note_span/record from 8 writers must never corrupt the rings."""
    rec = PipelineRecorder(maxlen=512)
    errs = []

    def hammer(tid):
        try:
            for i in range(200):
                rec.note_span("device", i, i + tid * 0.01,
                              i + tid * 0.01 + 0.5)
                rec.record("drained", i)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert len(rec.spans()) == 512
    assert rec.device_occupancy()["fraction"] is not None
    rec.bubble_attribution()


def test_recorder_gantt_occupancy_bubbles_exact():
    """Injected-clock math, no sleeps: two batches with known spans
    give an exact device-busy fraction, exact per-gap bubble
    attribution naming the starving stage, and index-ordered gantt
    rows that keep the widest interval for a re-noted stage."""
    rec = PipelineRecorder()
    rec.note_span("stack", 0, 0.0, 1.0)
    rec.note_span("dispatch", 0, 1.0, 2.0)
    rec.note_span("device", 0, 2.0, 4.0)
    rec.note_span("drain", 0, 4.0, 5.0)
    rec.note_span("stack", 1, 1.0, 3.0)
    rec.note_span("dispatch", 1, 3.0, 4.0)
    rec.note_span("device", 1, 4.0, 7.0)
    rec.note_span("drain", 1, 7.0, 8.0)

    occ = rec.device_occupancy()
    assert occ["window"] == [0.0, 8.0]
    assert occ["busy_seconds"] == pytest.approx(5.0)   # [2,7] union
    assert occ["fraction"] == pytest.approx(5.0 / 8.0)
    assert occ["stages"]["stack"] == pytest.approx(3.0 / 8.0)

    bub = rec.bubble_attribution()
    # Gaps: [0,2] (stack covers 2s of it, dispatch 1s) and [7,8]
    # (drain covers all 1s).  Starving stage = stack.
    assert bub["bubble_seconds"] == pytest.approx(3.0)
    assert bub["by_stage"]["stack"] == pytest.approx(2.0)
    assert bub["by_stage"]["dispatch"] == pytest.approx(1.0)
    assert bub["by_stage"]["drain"] == pytest.approx(1.0)
    assert bub["starving_stage"] == "stack"

    g = rec.gantt()
    assert [row["index"] for row in g] == [0, 1]
    assert g[0]["stages"]["device"] == [2.0, 4.0]
    # Split stack segments (the pool-wait exclusion pattern) widen.
    rec.note_span("stack", 0, 0.5, 1.5)
    assert rec.gantt()[0]["stages"]["stack"] == [0.0, 1.5]


def test_pipeline_collapse_emits_rate_limited_device_slow():
    """Three consecutive collapsed runs trip the streak and emit ONE
    device.slow (warn); further collapsed runs inside the rate-limit
    window stay silent; a healthy run resets the streak."""
    now = [100.0]
    ledger = roofline.RooflineLedger(clock=lambda: now[0])
    bad = PipelineRecorder()
    bad.note_span("dispatch", 0, 0.0, 9.0)
    bad.note_span("device", 0, 9.0, 10.0)     # 10% busy
    good = PipelineRecorder()
    good.note_span("device", 0, 0.0, 9.0)
    good.note_span("drain", 0, 9.0, 10.0)     # 90% busy

    seq0 = JOURNAL._seq

    def slow_events():
        return [e for e in JOURNAL.snapshot(type_="device.slow")
                if e["seq"] > seq0]

    for _ in range(3):
        ledger.note_pipeline("encode", bad, node="t:0")
    evs = slow_events()
    assert len(evs) == 1
    assert evs[0]["severity"] == "warn"
    assert evs[0]["attrs"]["pipeline"] == "encode"
    assert evs[0]["attrs"]["occupancy"] == pytest.approx(0.1)
    assert evs[0]["attrs"]["starving_stage"] == "dispatch"

    # Still collapsed but inside _EMIT_EVERY: no fresh event.
    ledger.note_pipeline("encode", bad)
    assert len(slow_events()) == 1
    # Past the window: one more.
    now[0] += roofline._EMIT_EVERY + 1.0
    ledger.note_pipeline("encode", bad)
    assert len(slow_events()) == 2

    occ = ledger.occupancy_summary()
    assert occ["any_collapsed"] and occ["collapsed"]["encode"]
    assert occ["latest"]["encode"]["fraction"] == pytest.approx(0.1)
    assert occ["latest"]["encode"]["starving_stage"] == "dispatch"

    ledger.note_pipeline("encode", good)
    occ = ledger.occupancy_summary()
    assert not occ["any_collapsed"]
    assert occ["latest"]["encode"]["fraction"] == pytest.approx(0.9)


# -- live cluster: surfaces + promcheck --------------------------------------

@pytest.fixture
def cluster(tmp_path):
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    master = MasterServer(volume_size_limit_mb=64,
                          meta_dir=str(tmp_path / "meta"),
                          pulse_seconds=60)
    master.start()
    d = tmp_path / "vs0"
    d.mkdir()
    vs = VolumeServer(master.url(), [str(d)], max_volume_counts=[10],
                      pulse_seconds=60)
    vs.start()
    yield master, vs
    vs.stop()
    master.stop()
    roofline.LEDGER.reset()


def _seed_ledger():
    """One real interpret-mode encode plus an injected-clock collapsed
    pipeline folded into the process ledger — the device plane's full
    surface without a heavyweight streamed workload."""
    pc = PallasCoder(4, 2)
    pc.encode(np.ones((4, 2048), np.uint8))
    rec = PipelineRecorder()
    rec.note_span("stack", 0, 0.0, 8.0)
    rec.note_span("dispatch", 0, 8.0, 9.0)
    rec.note_span("device", 0, 9.0, 10.0)
    for _ in range(roofline._COLLAPSE_STREAK):
        roofline.LEDGER.note_pipeline("encode", rec, node="seed:0")


def test_debug_and_cluster_device_surfaces(cluster):
    """The acceptance gate: a recorded encode + collapsed streamed
    pipeline show up on /debug/device (volume AND master), roll up
    through the heartbeat into /cluster/device with a collapse
    warning, mark healthz's device section (warning, never 503-worthy
    by itself), and render through cluster.roofline."""
    master, vs = cluster
    _seed_ledger()

    doc = rpc.call(f"http://{vs.url()}/debug/device")
    assert doc["armed"] is True and doc["role"] == "volume"
    kernels = {r["kernel"] for r in doc["kernels"]}
    assert "encode_kernel" in kernels
    assert doc["conservation"]["ok"], doc["conservation"]
    occ = doc["occupancy"]["latest"]["encode"]
    assert occ["fraction"] == pytest.approx(0.1)
    assert occ["starving_stage"] == "stack"
    assert doc["pipelines"][-1]["gantt"], "gantt missing"

    # The role-generic mount answers on the master too.
    mdoc = rpc.call(f"{master.url()}/debug/device")
    assert mdoc["role"] == "master" and "kernels" in mdoc

    vs._send_heartbeat(full=True)
    cdoc = rpc.call(f"{master.url()}/cluster/device")
    assert vs.url() in cdoc["nodes"]
    merged = {r["kernel"] for r in cdoc["kernels"]}
    assert "encode_kernel" in merged
    assert any("collapsed" in w for w in cdoc["warnings"]), cdoc
    row = next(r for r in cdoc["kernels"]
               if r["kernel"] == "encode_kernel")
    assert row["count"] >= 1 and row["bytes"] > 0 and row["work"] > 0
    # ?kernel= filters; an uncataloged name is a loud error.
    fdoc = rpc.call(
        f"{master.url()}/cluster/device?kernel=batch_encode")
    assert all(r["kernel"] == "batch_encode" for r in fdoc["kernels"])
    with pytest.raises(Exception):
        rpc.call(f"{master.url()}/cluster/device?kernel=bogus")

    status, hdoc = rpc.call_status(f"{master.url()}/cluster/healthz")
    assert isinstance(hdoc, dict) and "device" in hdoc
    assert any("collapsed" in w for w in hdoc["device"]["warnings"])
    assert any(r["pipeline"] == "encode"
               for r in hdoc["device"]["occupancy"])
    # Occupancy collapse alone is a warning, not a health problem.
    assert not any("occupancy" in p for p in hdoc["problems"])

    env = CommandEnv(master.url())
    out = run_command(env, "cluster.roofline")
    assert "encode_kernel" in out and "WORK" in out
    assert "starved by stack" in out
    assert "!!" in out


def test_promcheck_roofline_instruments_all_roles(cluster):
    """Every new instrument scrapes promcheck-clean on master and
    volume server, and the occupancy gauge carries the stage label."""
    master, vs = cluster
    _seed_ledger()
    mtext = bytes(rpc.call(f"{master.url()}/metrics")).decode()
    vtext = bytes(rpc.call(f"http://{vs.url()}/metrics")).decode()
    for text, who in ((mtext, "master"), (vtext, "volume")):
        assert validate_exposition(text) == [], f"{who} scrape dirty"
        for fam in ("SeaweedFS_kernel_seconds_total",
                    "SeaweedFS_kernel_bytes_total",
                    "SeaweedFS_kernel_work_total",
                    "SeaweedFS_device_occupancy"):
            assert fam in text, (who, fam)
    assert 'kernel="encode_kernel"' in vtext
    assert 'stage="device"' in vtext
