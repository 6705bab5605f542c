"""The benchmark's own arithmetic and data, held to known answers on the
CPU: the manifest and the files it names, the plain reference, the work
functions and the table of peaks, the reduction of a trace, the request
window's statistics.  No test here starts a server."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import data, ecref, loadgen, manifest, tracing, work  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "seal_1volume.xplane.pb")
MAN = manifest.load()
NAME, UNIT = manifest.NAME, manifest.UNIT


# -- the manifest and what it names -----------------------------------------

def test_manifest_keys_names_and_units_are_legal():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MAN[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer"),
                          kind, entry["name"]))
    metric_names = [n for is_metric, _k, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for kind in ("end_to_end", "per_layer"):
        for m in MAN[kind]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in manifest.SOURCES
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])
    assert len(json.dumps(MAN)) < 64 * 1024


def test_every_cell_has_its_files_and_its_metrics():
    cells = [w["name"] for w in MAN["workloads"]]
    assert len({(w["config"], w["traffic"])
                for w in MAN["workloads"]}) == len(cells)
    for w in MAN["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = manifest.cell(MAN, w["name"])
        for key in ("why", "sent_by"):
            assert cell["traffic"][key], (w["traffic"], key)
        assert cell["traffic"].get("jobs") or cell["traffic"].get("requests")
        e2e = [m["name"] for m in manifest.metrics_of(MAN, w["name"],
                                                      "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = manifest.metrics_of(MAN, w["name"], "per_layer")
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for c in MAN["configs"]:
        assert any(w["config"] == c["name"] for w in MAN["workloads"])
        assert c["file"].startswith(MAN["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        assert cfg["assumed"] and cfg["guarantees"]
    for m in MAN["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= set(cells)
        assert callable(manifest.reader(MAN, m["name"]))


def test_roofline_metrics_are_named_and_sourced_as_the_contract_says():
    for m in MAN["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            assert m["source"] == "device_trace"


def test_importing_the_harness_loads_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.run, benchmark.control, benchmark.tracing, "
            "benchmark.served, benchmark.loadgen; "
            "assert 'jax' not in sys.modules and "
            "'libtpu' not in sys.modules" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_four_chip_cells_are_few_and_say_why():
    """Nothing of this store exists only across chips: a cell that
    holds the whole host does so for steadiness, and says so."""
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 2)
    for w in four:
        assert "4 chips for steadiness alone" in w["why"], w["name"]


def test_the_servers_environment_takes_allocator_variables_only():
    from benchmark.machine import BenchFailure
    from benchmark.served import server_environment
    base = {"PATH": "/bin", "SEAWEEDFS_TPU_CODER": "numpy",
            "MALLOC_ARENA_MAX": "8"}
    env = server_environment(False, None, base)
    assert "SEAWEEDFS_TPU_CODER" not in env and env["PATH"] == "/bin"
    assert env["MALLOC_ARENA_MAX"] == "8" and "JAX_PLATFORMS" not in env
    env = server_environment(False, {"MALLOC_ARENA_MAX": "1",
                                     "MALLOC_TRIM_THRESHOLD_": "1024"}, base)
    assert env["MALLOC_ARENA_MAX"] == "1"
    assert env["MALLOC_TRIM_THRESHOLD_"] == "1024"
    assert server_environment(True, None, base)["JAX_PLATFORMS"] == "cpu"
    for smuggled in ({"SEAWEEDFS_TPU_EC_FUSED_CRC": "0"},
                     {"JAX_PLATFORMS": "cpu"}, {"XLA_FLAGS": "--x"},
                     {"LD_PRELOAD": "libjemalloc.so"},
                     {"MALLOC_ARENA_MAX": 1}):
        with pytest.raises(BenchFailure):
            server_environment(False, smuggled, base)


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_a_configurations_server_env_is_the_allocators(config):
    """What a configuration says of its server's environment starts
    the server; `assumed` says why it is there."""
    from benchmark.served import server_environment
    entry = next(c for c in MAN["configs"] if c["name"] == config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    said = cfg.get("server_env")
    env = server_environment(False, said, {})
    assert all(env[k] == v for k, v in (said or {}).items())
    assert (said is None) or "server_env" in cfg["assumed"]


# -- inputs from the seed -----------------------------------------------------

def test_every_seed_gives_the_same_set_of_sizes_in_another_order():
    a = data.needle_sizes(1, 4096, 1 << 20, 37 << 20)
    b = data.needle_sizes(2**31 + 12345, 4096, 1 << 20, 37 << 20)
    assert sum(a) == sum(b) == 37 << 20
    assert sorted(a) == sorted(b) and a != b
    assert a == data.needle_sizes(1, 4096, 1 << 20, 37 << 20)
    assert min(a) >= 1 and max(a) <= 1 << 20


def test_request_payloads_repeat_and_differ():
    block = data.payload_block(7)
    p = data.request_payload(block, (3 << 32) | 5, 1024)
    assert len(p) == 1024 and p == data.request_payload(
        block, (3 << 32) | 5, 1024)
    assert p != data.request_payload(block, (3 << 32) | 6, 1024)
    assert block != data.payload_block(8)


# -- the plain reference ------------------------------------------------------

def test_reference_code_is_the_programs_reference_code():
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    rows = np.random.default_rng(0).integers(0, 256, (10, 4099), np.uint8)
    oracle = NumpyCoder(10, 4)
    parity = ecref.encode(rows)
    assert np.array_equal(parity, oracle.encode(rows))
    full = np.concatenate([rows, parity])
    have = {s: full[s] for s in range(14) if s not in (3, 11, 0, 13)}
    got = ecref.reconstruct(have, [3, 11])
    assert np.array_equal(got[3], full[3])
    assert np.array_equal(got[11], full[11])
    broken = ecref.encode(rows, broken=True)
    assert np.array_equal(broken[:3], parity[:3])
    assert not np.array_equal(broken[3], parity[3])
    assert np.array_equal(broken[3], np.bitwise_xor.reduce(rows, axis=0))


def test_reference_crc_is_castagnoli():
    assert ecref.crc32c_plain(b"123456789") == 0xE3069283
    buf = np.random.default_rng(1).bytes(70001)
    assert ecref.crc32c(buf) == ecref.crc32c_plain(buf)
    from seaweedfs_tpu.core.crc import crc32c
    assert ecref.crc32c(buf) == crc32c(buf)


def test_shard_layout(tmp_path):
    dat = tmp_path / "v.dat"
    raw = np.random.default_rng(2).bytes(12 * ecref.BLOCK + 17)
    dat.write_bytes(raw)
    assert ecref.shard_size(len(raw)) == 2 * ecref.BLOCK
    row1 = ecref.dat_row(str(dat), 1)
    assert row1.shape == (10, ecref.BLOCK)
    assert row1[1].tobytes() == raw[11 * ecref.BLOCK:12 * ecref.BLOCK]
    assert row1[2][:17].tobytes() == raw[12 * ecref.BLOCK:]
    assert not row1[2][17:].any() and not row1[3].any()


# -- what a call has to do, and the peaks ---------------------------------------

def test_work_of_an_encode_chunk_by_hand():
    n = 4 << 20
    assert work.coder_bytes(10, 4, n) == 58_720_256
    assert work.coder_ops(10, 4, n) == 21_474_836_480
    least = work.least_seconds(10, 4, n, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(58_720_256 / 819e9)
    assert 21_474_836_480 / 393e12 < least["seconds"]


def test_work_of_a_two_row_rebuild_by_hand():
    n = 4 << 20
    assert work.coder_bytes(10, 2, n) == 50_331_648
    assert work.coder_ops(10, 2, n) == 2 * 16 * 80 * n
    assert work.least_seconds(10, 2, n, "TPU v5 lite")["seconds"] == \
        pytest.approx(50_331_648 / 819e9)


def test_peaks_table_raises_on_an_unknown_device():
    assert work.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
    with pytest.raises(KeyError):
        work.least_seconds(10, 4, 1, "cpu")


# -- from a trace to numbers ----------------------------------------------------

def test_reduce_on_events_made_by_hand():
    device = {"/device:TPU:0": [("kernel.1", 1.0, 0.5),
                                ("fusion.3", 1.25, 0.5),     # overlaps
                                ("kernel.1", 3.0, 1.0)]}
    host = [("PjitFunction(f)", 0.0, 0.9), ("np.asarray(x)", 2.0, 0.75)]
    out = tracing.reduce(device, host)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.75 + 1.0)
    assert out["span_s"] == pytest.approx(4.0)
    assert out["ops"]["kernel.1"] == pytest.approx(1.5)
    assert tracing.kernel_seconds(out["ops"], "kernel") == pytest.approx(1.5)
    gaps = dict(out["gaps"])
    # idle: [0, 1) under the jitted call for 0.9, [1.75, 3) under
    # np.asarray for 0.75; the rest with no JAX span on the host
    assert gaps["PjitFunction_f_"] == pytest.approx(0.9)
    assert gaps["np.asarray_x_"] == pytest.approx(0.75)
    assert gaps[tracing.OUTSIDE] == pytest.approx(0.1 + 0.5)
    assert sum(gaps.values()) == pytest.approx(4.0 - 1.75)
    assert tracing.top_ops(out["ops"])[0] == ["kernel", pytest.approx(1.5)]


def test_reduce_without_a_device_finds_nothing():
    out = tracing.reduce({}, [("x", 0.0, 1.0)])
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_recorded_trace_of_one_sealed_volume():
    """chiprun, TPU v5 lite, PR 24: one 1040 MiB volume sealed inside the
    traced window — 26 calls of the fused-CRC encode kernel on (10, 4 MiB)
    chunks, 1.4984 ms each."""
    device, host = tracing.load(TRACE)
    assert list(device) == ["/device:TPU:0"]
    kernel = [e for e in device["/device:TPU:0"]
              if e[0].startswith("apply_bitmatrix_crc_pallas")]
    assert len(kernel) == 26
    out = tracing.reduce(device, host)
    assert out["busy_s"] == pytest.approx(0.039013724, rel=1e-6)
    assert tracing.kernel_seconds(out["ops"], "apply_bitmatrix") == \
        pytest.approx(0.038959273, rel=1e-6)
    assert out["span_s"] == pytest.approx(1.676974973, rel=1e-6)
    assert sum(s for _n, s in out["gaps"]) == pytest.approx(
        out["span_s"] - out["busy_s"], rel=1e-6)
    assert out["gaps"][0][0] == tracing.OUTSIDE
    read = manifest.reader(MAN, "encode_kernel_roofline")
    facts = {"jobs": {"op": "ec.encode", "count": 1,
                      "shard_bytes": 104 << 20, "lost": 2},
             "trace": out, "device_kind": "TPU v5 lite"}
    least = 26 * 58_720_256 / 819e9
    assert read(facts) == pytest.approx(100 * least / 0.038959273)
    assert 4.0 < read(facts) < 6.0
    assert read(dict(facts, trace=None)) is None
    idle = manifest.reader(MAN, "seal_device_idle_share")
    assert idle(dict(facts, traced_s=1.7)) == pytest.approx(
        100 * (1 - 0.039013724 / 1.7))


def test_op_names():
    raw = ("%apply_bitmatrix_crc_pallas.1 = (u8[4,4194304]{1,0:T(4,128)"
           "(4,1)}, s32[64,128]) custom-call(s8[32,80] %convert)")
    assert tracing.op_name(raw) == "apply_bitmatrix_crc_pallas.1"
    assert tracing.stable("fusion.12") == "fusion"
    assert tracing.stable("PjitFunction(f)") == "PjitFunction_f_"


# -- the request window's statistics ----------------------------------------------

def test_window_statistics_count_a_failure_as_slower_than_any():
    n = 1000
    res = {"done": np.linspace(10.0, 20.0, n, endpoint=False),
           "lat": np.full(n, 0.010), "cpu_s": 2.0,
           "ok": np.zeros(n, np.int8), "who": np.arange(n) % 16}
    res["ok"][::10] = loadgen.FAILED          # a tenth of them fail
    res["lat"][1::10] = 0.050
    got = loadgen.in_window(res, 12.0, 17.0, cores=4)
    assert got["attempted"] == 500 and got["failed"] == 50
    assert got["req_per_s"] == pytest.approx(450 / 5.0)
    assert got["p50_ms"] == pytest.approx(10.0)
    assert got["p95_ms"] == float("inf")
    assert got["client_cpu_share"] == pytest.approx(100 * 2.0 / 20.0)
    assert got["clients_active"] == 16
    assert got["longest_stall_ms"] == pytest.approx(10.0)
    assert 0.9 < got["least_client_share"] <= 1.0
    res["ok"][:] = loadgen.GOOD
    got = loadgen.in_window(res, 12.0, 17.0, cores=4)
    assert got["p95_ms"] == pytest.approx(50.0)
    assert got["p50_ms"] == pytest.approx(10.0)


def test_lost_bytes_of_three_needles_made_by_hand():
    """Block b of the volume is block b // 10 of shard b % 10: a record
    inside a healthy block, one inside shard 3's block of the second
    row, and one that straddles shard 2's block and shard 3's."""
    mib = ecref.BLOCK
    healthy = (5 * mib + 4096, ecref.record_bytes(70000))
    inside = (13 * mib + 8, ecref.record_bytes(300000))
    straddling = (3 * mib - 1000, ecref.record_bytes(5000))
    assert ecref.record_bytes(70000) == 70032      # 28 around, 4 padding
    assert ecref.record_bytes(5000) == 5032
    assert ecref.record_bytes(4) == 40             # a whole 8 of padding
    lost = [3, 11]
    assert ecref.lost_bytes(*healthy, lost) == 0
    assert ecref.lost_bytes(*inside, lost) == inside[1] == 300032
    assert ecref.lost_bytes(*straddling, lost) == 5032 - 1000
    # a lost parity shard holds no byte of the volume; four lost data
    # shards hold what lies in their blocks, to the byte
    assert ecref.lost_bytes(0, 20 * mib, [11, 13]) == 0
    assert ecref.lost_bytes(mib // 2, 4 * mib, [0, 1, 2, 3]) == \
        7 * mib // 2
    assert ecref.lost_bytes(9 * mib + 5, 2 * mib, [0, 9]) == 2 * mib - 5

    # what a window's reads add up to (run.py `pool_facts`): two volumes
    # of those three needles, keys 0-5; key 4 is volume 2's `inside`
    from benchmark import run
    gone = [ecref.lost_bytes(*r, lost) for r in (healthy, inside,
                                                 straddling)]
    ctx = {"key_facts": {"bytes": np.tile([70000, 300000, 5000], 2),
                         "lost_bytes": np.tile(gone, 2)},
           "res": {"ids": np.array([0, 4, 2, -1, 4, 1, 3]),
                   "ok": np.array([0, 0, 1, 2, 0, 0, 0])},
           "req": {"inside": np.array([1, 1, 1, 1, 1, 0, 1], bool)}}
    assert run.pool_facts(ctx) == {
        "pool_reads": 5, "pool_read_bytes": 70000 * 2 + 300000 * 2 + 5000,
        "pool_lost_bytes": 300032 * 2 + 4032,
        "pool_reads_on_lost_shards": 3}


def test_index_entries_and_the_record_header(tmp_path):
    path = tmp_path / "v.idx"
    path.write_bytes(ecref.INDEX_ENTRY.pack(7, 1, 100)
                     + ecref.INDEX_ENTRY.pack(0x1234, 131072, 4200)
                     + ecref.INDEX_ENTRY.pack(7, 17, -1))
    assert ecref.index_entries(str(path)) == {7: (136, -1),
                                              0x1234: (1 << 20, 4200)}
    assert ecref.RECORD_HEADER.size == 16 and ecref.INDEX_ENTRY.size == 16


def test_work_of_a_read_that_meets_lost_bytes_by_hand():
    # 1000 lost bytes under RS(10,4): ten survivors' intervals in and
    # one out; one output row of 8 bit-planes against 80
    assert work.lost_read_bytes(10, 1000) == 11000
    assert work.lost_read_ops(10, 1000) == 2 * 8 * 80 * 1000
    assert work.lost_read_bytes(10, 1000) == work.coder_bytes(10, 1, 1000)


def test_per_layer_readers_on_facts_made_by_hand():
    facts = {"requests": {"attempted": 1000, "failed": 0, "op": "write",
                          "p99_ms": 12.5, "client_cpu_share": 9.0,
                          "longest_stall_ms": 31.0},
             "server_cpu_s": 2.0, "jobs": None, "trace": None,
             "compiles": {"count": 0, "seconds": 0.0}, "coder_rows": {},
             "window_s": 10.0}

    def read(name):
        return manifest.reader(MAN, name)(facts)

    assert read("server_cpu_us_per_req") == pytest.approx(2000.0)
    assert read("write_p99_ms") == 12.5 and read("read_p99_ms") is None
    assert read("client_cpu_share") == 9.0
    assert read("longest_stall_ms") == 31.0
    assert read("req_compiles_in_window") == 0
    assert read("req_device_idle_share") is None
    assert read("encode_kernel_roofline") is None
    facts.update(requests=None, jobs={"op": "ec.encode", "count": 2,
                                      "shard_bytes": 1, "lost": 2},
                 coder_rows={"encode_crc_kernel": {"seconds": 2.5}})
    assert read("server_cpu_us_per_req") is None
