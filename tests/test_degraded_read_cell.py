"""Reads while shards are gone (PERF.md, configuration
`warm-ec-degraded-read`, cell `degraded-read`), in the pattern of
tests/test_seal_under_load.py and tests/test_rebuild_under_load.py:

- the configuration, the traffic file, the cell and the thirteen
  per-layer entries are there with the issue's parameters, appended:
  nothing the benchmark had names the cell, lists it or moved;
- every new reader on facts made by hand: a number where its inputs are
  there, None where they are missing — except `degraded_device_ms`, which
  REFUSES a run whose server answered reads on lost shards and left no
  `read.dispatch` row (a program from before the rung gives no reading
  in this cell);
- one rehearsal of the cell on the CPU platform (a real `server`, the
  Pallas coder in interpret mode): correct, and no compilation counted
  inside its window.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "bench"))

from _bench_util import has_result_line, rehearsal_result, rehearse  # noqa: E402
from benchmark import manifest  # noqa: E402
from benchmark.machine import BenchFailure  # noqa: E402

MAN = manifest.load()
CELL, CONFIG, TRAFFIC = ("degraded-read", "warm-ec-degraded-read",
                         "read-pool-degraded")
VOLUME_ENGINE = "rpc plane + volume engine"
# name -> (unit, source, layer), in the manifest's order
ENTRIES = {
    "degraded_read_ms": ("ms", "program_span", VOLUME_ENGINE),
    "healthy_read_ms": ("ms", "program_span", VOLUME_ENGINE),
    "degraded_read_share": ("%", "program_counter", VOLUME_ENGINE),
    "degraded_gather_ms": ("ms", "program_span", VOLUME_ENGINE),
    "degraded_device_ms": ("ms", "program_span", "Pallas coder"),
    "degraded_intervals_per_launch": ("count", "program_counter",
                                      VOLUME_ENGINE),
    "degraded_kernel_roofline": ("%", "device_trace", "Pallas coder"),
    "read_p99_ms": ("ms", "host_clock", VOLUME_ENGINE),
    "read_server_cpu_us_per_req": ("us/op", "program_counter",
                                   VOLUME_ENGINE),
    "read_longest_stall_ms": ("ms", "host_clock", VOLUME_ENGINE),
    "read_client_cpu_share": ("%", "host_clock", "load generator"),
    "read_compiles_in_window": ("count", "program_counter", "jit / shapes"),
    "read_device_idle_share": ("%", "device_trace", "device"),
}
ACCEPTED_CELLS = ["seal", "rebuild", "bench-write-1k", "seal-under-load",
                  "rebuild-under-load"]


def test_the_cell_is_entries_appended_and_files_added():
    configs = [c["name"] for c in MAN["configs"]]
    assert configs[4:5] == [CONFIG]
    cells = [w["name"] for w in MAN["workloads"]]
    assert cells[:5] == ACCEPTED_CELLS and cells[5:6] == [CELL]
    w = MAN["workloads"][5]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "4 chips" not in w["why"]
    names = [m["name"] for m in MAN["per_layer"]]
    first = names.index("degraded_read_ms")
    assert names[first:first + len(ENTRIES)] == list(ENTRIES)
    assert first > names.index("load_rebuild_reader_ms_per_chunk")
    for m in MAN["per_layer"][first:first + len(ENTRIES)]:
        unit, source, layer = ENTRIES[m["name"]]
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
        assert m["moves"] == "req_p95_ms" and m["workloads"] == [CELL]
    # nothing accepted names the cell but the one tail it is held to
    for m in MAN["per_layer"]:
        assert (CELL in m["workloads"]) == (m["name"] in ENTRIES)
    listed = {m["name"] for m in MAN["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert listed == {"req_p95_ms"}
    p95 = next(m for m in MAN["end_to_end"] if m["name"] == "req_p95_ms")
    assert p95["workloads"][:2] == ["bench-write-1k", CELL]
    assert p95["bound"] == 0.15
    got = {m["name"] for kind in ("end_to_end", "per_layer")
           for m in manifest.metrics_of(MAN, CELL, kind)}
    # `req_per_s` is read under `seen`, not listed: one listed tail
    assert got == {"req_p95_ms", "setup_s", *ENTRIES}


def test_the_configuration_and_the_traffic_are_the_issues():
    cell = manifest.cell(MAN, CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert traffic["requests"] == {"op": "read", "keys_from": "pool",
                                   "warm_seconds": 3}
    assert traffic["jobs"] == {"op": "ec.rebuild", "volumes": 4,
                               "repeat": 0}
    assert "ec" not in traffic and traffic["why"] and traffic["sent_by"]
    # the published shapes, kept
    assert (cfg["data_shards"], cfg["parity_shards"]) == (10, 4)
    assert cfg["large_block_bytes"] == 1 << 30
    assert cfg["small_block_bytes"] == 1 << 20
    assert (cfg["clients"], cfg["procs"]) == (16, 4)
    assert cfg["needle_bytes"] == [4096, 4194304]
    assert cfg["lost_shards"] == [0, 1, 2, 3]
    assert cfg["volume_bytes"] == 545259520
    # glibc's defaults, as the two live configurations
    assert "server_env" not in cfg
    assert cfg["reduced"] == ["volume_bytes", "n"]
    assert set(cfg["assumed"]) >= {"needle_bytes", "lost_shards", "pool",
                                   "procs"}
    said = " ".join(cfg["guarantees"])
    for number in ("answers_differ", "lost_shards_back", "requests_failed"):
        assert number in said
    # every value the sealing configuration has, this one has too
    parent = manifest.cell(MAN, "rebuild")["config"]
    for key, value in parent.items():
        if key in ("name", "source", "deployment", "guarantees", "reduced",
                   "reduced_why", "assumed", "lost_shards", "server_env"):
            continue
        assert cfg[key] == value, key
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert "Erasure-Coding-for-warm-storage" in cfg["source"]
    assert "Benchmark" in cfg["source"] and len(cfg["source"]) <= 200


# -- the readers on facts made by hand ------------------------------------------

ROWS = {"read.degraded": (1300, 39.0), "read.healthy": (1500, 18.0),
        "read.gather": (1700, 5.2), "read.dispatch": (1700, 6.5),
        "read.drain": (1700, 10.4), "read.interval": (2210, 22.1)}
REQUESTS = {"op": "read", "attempted": 2800, "failed": 0, "p99_ms": 99.5,
            "longest_stall_ms": 21.0, "client_cpu_share": 3.5,
            "pool_reads": 2800, "pool_read_bytes": 1_700_000_000,
            "pool_lost_bytes": 700_000_000,
            "pool_reads_on_lost_shards": 1316}
WANT = {"degraded_read_ms": 30.0, "healthy_read_ms": 12.0,
        "degraded_read_share": 47.0,
        "degraded_gather_ms": 4.0,             # 5.2 s over 1300 GETs
        "degraded_device_ms": 13.0,            # 6.5 + 10.4 s over 1300
        "degraded_intervals_per_launch": 1.3,
        "read_p99_ms": 99.5,
        "read_server_cpu_us_per_req": 10000.0,
        "read_longest_stall_ms": 21.0, "read_client_cpu_share": 3.5,
        "read_compiles_in_window": 0,
        "read_device_idle_share": 99.0,
        # (10 + 1) * 7e8 bytes at 819 GB/s over 0.1 s of kernel
        "degraded_kernel_roofline": 100.0 * 11 * 7e8 / 819e9 / 0.1}


def facts(rows: dict, requests: dict | None = REQUESTS,
          trace: bool = True) -> dict:
    return {"window_s": 10.0, "traced_s": 14.0, "jobs": None,
            "device_kind": "TPU v5 lite", "server_cpu_s": 28.0,
            "compiles": {"count": 0, "seconds": 0.0},
            "requests": requests,
            "trace": {"devices": 1, "busy_s": 0.14,
                      "ops": {"apply_bitmatrix_pallas": 0.1,
                              "convert_element_type": 0.001}}
            if trace else None,
            "coder_rows": {k: {"count": c, "seconds": s, "bytes": 1}
                           for k, (c, s) in rows.items()}}


@pytest.mark.parametrize("name,want", WANT.items())
def test_readers_on_facts_made_by_hand(name, want):
    read = manifest.reader(MAN, name)
    assert read(facts(ROWS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n in ENTRIES
                                  if n != "degraded_device_ms"])
def test_a_reader_without_its_inputs_reads_none(name):
    """The parent's program serves no `read.` row; a write mix has no
    pool facts; an untraced run no trace: the metric is left out."""
    read = manifest.reader(MAN, name)
    write = {"op": "write", "attempted": 8000, "failed": 0, "p99_ms": 60.0,
             "longest_stall_ms": 9.0, "client_cpu_share": 5.0}
    for without in (facts({}, None, False), facts({}, write, False)):
        assert read(without) is None, name
    if ENTRIES[name][1] == "device_trace":
        assert read(facts(ROWS, trace=False)) is None
    if name.startswith(("degraded_read_ms", "healthy", "degraded_gather",
                        "degraded_intervals")):
        assert read(facts({}, REQUESTS)) is None


def test_the_device_reader_refuses_a_program_without_the_rung():
    read = manifest.reader(MAN, "degraded_device_ms")
    # the parent in this cell: reads on lost shards answered, no row
    with pytest.raises(BenchFailure, match="device rung.*some other way"):
        read(facts({}))
    with pytest.raises(BenchFailure, match="read.dispatch"):
        read(facts({k: v for k, v in ROWS.items()
                    if k not in ("read.dispatch", "read.drain")}))
    # a draw that met no lost shard, another mix, no requests: no verdict
    assert read(facts({}, dict(REQUESTS, pool_reads_on_lost_shards=0))) \
        is None
    assert read(facts({}, {"op": "write", "attempted": 1, "failed": 0})) \
        is None
    assert read(facts({}, None)) is None
    # with the row, the number; at --trace 0 too (report() calls every
    # reader in every run)
    assert read(facts(ROWS, trace=False)) == pytest.approx(13.0)


def test_the_kernels_share_never_counts_more_than_the_lost_bytes():
    """One row out a lost byte, ten survivors in, whatever the launches
    pad to: `work.lost_read_bytes` of the harness's own count."""
    from benchmark import work
    read = manifest.reader(MAN, "degraded_kernel_roofline")
    n = REQUESTS["pool_lost_bytes"]
    least = work.least_seconds(10, 1, n, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        work.lost_read_bytes(10, n) / 819e9)
    assert read(facts(ROWS)) == pytest.approx(100.0 * least["seconds"] / 0.1)
    assert 0 < read(facts(ROWS)) < 100


# -- the cell rehearsed ------------------------------------------------------------

def test_the_cell_rehearses_correct_and_compiles_nothing_in_its_window():
    rc, out, err = rehearse("run.py", CELL, 2**31 + 36, trace=0, seconds=4)
    assert rc == 0, err[-3000:]
    assert "platform=cpu" in out and not has_result_line(out)
    res = rehearsal_result(out)
    assert res["correct"] is True and res["failed"] == 0
    c, seen = res["compared"], res["seen"]
    assert c["requests_failed"] == [0, 0] and c["answers_differ"] == [0, 0]
    assert c["lost_shards_back"] == [0, 0]
    assert c["pool_reads_on_lost_shards"][0] > 0
    assert seen["read_compiles_in_window"] == 0
    # every reader that needs no device found its rows
    for name in ENTRIES:
        if ENTRIES[name][1] != "device_trace":
            assert name in seen, name
    assert 30 <= seen["degraded_read_share"] <= 70
    assert seen["degraded_intervals_per_launch"] >= 1.0
    assert json.dumps(res)      # one line's worth
