"""Every test of the benchmark that starts a server, in ONE file, so
that under `--dist loadfile` one worker runs them one after another and
the suite's timing tests keep their cores.  All on the CPU platform at a
tiny size (Pallas in interpret mode): a real `server` process, the
window, the comparison with the reference, the metrics.

- every cell of BENCHMARK.json rehearses end to end, prints platform=cpu
  and never a result line;
- the control of every cell's comparison comes out as NOT correct: the
  reference with one stated guarantee broken stands in the program's
  place (an XOR row where a Reed-Solomon row belongs; acknowledged
  writes torn), and `control.py` exits 0 only where `correct` read false;
- the rest of a run driven with the timed path broken underneath (a
  flipped byte, a job that does nothing; the request mixes' altered
  answer is the control's torn write);
- a later PR's cell arrives as new files and manifest entries only, and
  the read mix, whose files wait under `benchmark/`, as entries only;
  so does a cell whose clients read the needles of sealed volumes that
  lost shards (`keys_from: "pool"`), with its control.
"""

import filecmp
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from _bench_util import (ROOT, has_result_line, rehearsal_result,  # noqa: E402
                         rehearse)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MAN = json.load(_f)
CELLS = [w["name"] for w in MAN["workloads"]]
JOB_CELLS = [w["name"] for w in MAN["workloads"]
             if w["config"] == "warm-ec-rs10-4"]


def _reported(cell: str, kind: str) -> set:
    return {m["name"] for m in MAN[kind]
            if "workloads" not in m or cell in m["workloads"]}


# -- every cell rehearses ---------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_untraced(cell):
    rc, out, err = rehearse("run.py", cell, 2**31 + 7, trace=0)
    assert rc == 0, err[-3000:]
    assert "platform=cpu" in out and not has_result_line(out)
    res = rehearsal_result(out)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == _reported(cell, "end_to_end")
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    for name, (value, limit) in res["compared"].items():
        assert limit is None or value <= limit, name
        assert f"compared {name}: {value} (limit {limit})" in err


@pytest.mark.parametrize("cell", CELLS[1:])
def test_cell_rehearses_traced(cell):
    rc, out, err = rehearse("run.py", cell, 11, trace=1)
    assert rc == 0, err[-3000:]
    assert not has_result_line(out)
    res = rehearsal_result(out)
    assert res["correct"] is True
    # no device on the CPU platform: the readers of the trace return
    # nothing and are left out, never a 0 share
    got = set(res["metrics"])
    assert got and got <= _reported(cell, "per_layer")
    assert not any("roofline" in m or "idle" in m for m in got)
    assert any(m.endswith("compiles_in_window") for m in got)
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_no_accelerator_means_no_result_line():
    """Without the rehearsal flag the CPU platform is a failed run."""
    rc, out, err = rehearse("run.py", CELLS[0], 3, flags=(),
                            env={"JAX_PLATFORMS": "cpu"})
    assert rc != 0 and not has_result_line(out)
    assert "the server resolved" in err


def test_alone_in_a_directory_the_benchmark_fails(tmp_path):
    """BENCHMARK.json and the files under `paths`, nothing else."""
    root = tmp_path / "alone"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for p in MAN["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, _err = rehearse("run.py", CELLS[0], 4, root=str(root),
                             flags=())
    assert rc != 0 and not has_result_line(out)


# -- the control ----------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(cell):
    rc, out, err = rehearse("control.py", cell, 21)
    assert rc == 0, (out[-2000:], err[-3000:])
    assert "control: " in out
    res = rehearsal_result(out)
    assert res["correct"] is False
    over = {k: v for k, (v, lim) in res["compared"].items()
            if lim is not None and v > lim}
    assert over, res["compared"]
    if cell in JOB_CELLS:
        # only the comparison of parity with the reference can tell:
        # the control's `.ecc` follows its shard
        assert over.get("parity_blocks_differ") and \
            not res["compared"]["ecc_entries_differ"][0]


# -- the timed path broken underneath ----------------------------------------------

FAULT = os.path.join(HERE, "_fault_run.py")


@pytest.mark.parametrize("cell,sid", [("seal", 2), ("rebuild", 11)])
def test_an_altered_answer_is_not_correct(cell, sid):
    rc, out, err = rehearse(FAULT, cell, 31, seconds=1,
                            before=("flip", str(sid), "--"))
    assert rc == 1, err[-3000:]
    res = rehearsal_result(out)
    assert "platform=cpu" in out and res["correct"] is False
    c = res["compared"]
    assert c["ecc_entries_differ"][0] >= 1
    if cell == "rebuild":
        assert c["rebuilt_files_differ"][0] >= 1
    which = "data_blocks_differ" if sid < 10 else "parity_blocks_differ"
    assert c[which][0] >= 1


def test_a_job_that_leaves_its_state_unchanged_is_not_correct():
    rc, out, err = rehearse(FAULT, "seal", 32, seconds=1,
                            before=("nothing", "--"))
    assert rc == 1, err[-3000:]
    res = rehearsal_result(out)
    assert res["correct"] is False
    assert res["compared"]["files_missing"][0] >= 1


# -- additions as data ------------------------------------------------------------------

NEW_CONFIG = {
    "name": "warm-ec-small", "source": "a test's own deployment",
    "volume_bytes": 41943040, "needle_bytes": [4096, 1048576],
    "lost_shards": [0, 13], "guarantees": ["as warm-ec-rs10-4"],
    "reduced": ["volume_bytes"], "assumed": {"lost_shards": "0 and 13"}}
NEW_TRAFFIC = {
    "why": "two rebuilds of a small volume that lost its first data and "
           "its last parity shard",
    "sent_by": "a test",
    "jobs": {"op": "ec.rebuild", "repeat": 2, "metric": "rebuild_MBps"}}
NEW_METRIC = '''"""EC file pipeline: coder calls per rebuilt volume."""


def read(facts):
    jobs = facts["jobs"]
    calls = facts["coder_rows"].get("rebuild.dispatch", {}).get("count")
    return calls / jobs["count"] if jobs and calls else None
'''
POOL_CONFIG = {
    "name": "warm-ec-read-small", "source": "a test's own deployment",
    "volume_bytes": 41943040, "needle_bytes": [4096, 1048576],
    "lost_shards": [3, 11], "clients": 16, "procs": 4,
    "guarantees": ["as warm-ec-rs10-4", "any 10 shards give back every "
                   "needle, to a client that asks while shards are gone"],
    "reduced": ["volume_bytes"], "assumed": {"lost_shards": "3 and 11"}}
POOL_TRAFFIC = {
    "why": "16 clients read the needles of two small sealed volumes "
           "that lost a data and a parity shard",
    "sent_by": "a test",
    "requests": {"op": "read", "warm_seconds": 1, "keys_from": "pool"},
    "jobs": {"op": "ec.rebuild", "volumes": 2, "repeat": 0,
             "metric": "rebuild_MBps"}}
POOL_METRIC = '''"""EC file pipeline: bytes the window's reads had to have reconstructed."""


def read(facts):
    req = facts["requests"]
    return req.get("pool_lost_bytes") if req else None
'''


def _copy_of_the_benchmark(tmp_path):
    """(root of a temporary copy with the program linked in, a copy of
    the manifest, the files that are there)."""
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("seaweedfs_tpu", "native"):      # the program itself
        os.symlink(os.path.join(ROOT, name), root / name)
    before = [os.path.relpath(os.path.join(dp, p), root)
              for dp, _d, fs in os.walk(root / "benchmark") for p in fs]
    return root, json.loads(json.dumps(MAN)), before


def _nothing_edited(root, before):
    for rel in before:
        assert filecmp.cmp(root / rel, os.path.join(ROOT, rel),
                           shallow=False), rel


def test_the_read_mix_is_manifest_entries_only(tmp_path):
    """`bench-read-1k` waits for a steadier read path (PERF.md): its
    mix and its readers are under `benchmark/`, and entries in the
    manifest bring it back.  Its control fails its comparison."""
    root, man, before = _copy_of_the_benchmark(tmp_path)
    man["workloads"].append({
        "name": "bench-read-1k", "config": "weed-benchmark-1k",
        "traffic": "read-1k", "chips": 1, "why": "a test"})
    for m in man["end_to_end"]:
        if m["name"] == "req_per_s":
            m["workloads"].append("bench-read-1k")
    for name in ("read_p95_ms", "read_p99_ms"):
        man["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "rpc plane + volume engine",
            "moves": "req_per_s", "workloads": ["bench-read-1k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    rc, out, err = rehearse("run.py", "bench-read-1k", 8, trace=1,
                            root=str(root))
    assert rc == 0, err[-3000:]
    res = rehearsal_result(out)
    assert res["correct"] is True and res["attempted"] > 100
    assert set(res["metrics"]) == {"read_p95_ms", "read_p99_ms"}
    assert res["seen"]["clients_active"] == 16
    assert res["compared"]["answers_differ"] == [0, 0]
    rc, out, err = rehearse("control.py", "bench-read-1k", 9,
                            root=str(root))
    assert rc == 0, (out[-2000:], err[-3000:])
    c = rehearsal_result(out)["compared"]
    assert c["requests_failed"][0] + c["answers_differ"][0] > 0
    _nothing_edited(root, before)


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """In a temporary copy of the benchmark: one new configuration, one
    new traffic mix, one new per-layer metric reading a counter that is
    already there, found by name; no file that was there is edited."""
    root, man, before = _copy_of_the_benchmark(tmp_path)

    bench = root / "benchmark"
    (bench / "configs" / "warm-ec-small.json").write_text(
        json.dumps(NEW_CONFIG))
    (bench / "traffic" / "rebuild-two.json").write_text(
        json.dumps(NEW_TRAFFIC))
    (bench / "metrics" / "rebuild_calls_per_volume.py").write_text(
        NEW_METRIC)
    man["configs"].append({
        "name": "warm-ec-small", "source": NEW_CONFIG["source"],
        "file": "benchmark/configs/warm-ec-small.json",
        "reduced": ["volume_bytes"], "why": "a test"})
    man["workloads"].append({
        "name": "rebuild-small", "config": "warm-ec-small",
        "traffic": "rebuild-two", "chips": 1, "why": "a test"})
    for m in man["end_to_end"]:
        if m["name"] == "rebuild_MBps":
            m["workloads"].append("rebuild-small")
    man["per_layer"].append({
        "name": "rebuild_calls_per_volume", "unit": "count",
        "better": "lower", "source": "program_counter",
        "layer": "EC file pipeline", "moves": "rebuild_MBps",
        "workloads": ["rebuild-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    rc, out, err = rehearse("run.py", "rebuild-small", 5, trace=1,
                            root=str(root))
    assert rc == 0, err[-3000:]
    res = rehearsal_result(out)
    assert res["correct"] is True and res["attempted"] == 2
    # one (10, 4 MiB) dispatch per 40 MiB volume
    assert res["metrics"] == {"rebuild_calls_per_volume":
                              {"value": 1.0, "unit": "count"}}
    assert res["compared"]["rebuilt_files_differ"] == [0, 0]
    assert set(res["seen"]) >= {"rebuild_MBps", "setup_s"}
    _nothing_edited(root, before)


@pytest.mark.parametrize("repeat", [0, 1])
def test_a_pool_read_cell_is_files_and_entries_only(tmp_path, repeat):
    """In a temporary copy of the benchmark: a configuration, a traffic
    mix whose clients read the needles of sealed, damaged volumes, and
    one reader of a fact the harness computes for such a mix.  With
    `repeat` 0 no job runs in the window and the control comes out not
    correct; with 1 of 2 the first volume is rebuilt beside the reads."""
    root, man, before = _copy_of_the_benchmark(tmp_path)
    bench = root / "benchmark"
    traffic = json.loads(json.dumps(POOL_TRAFFIC))
    traffic["jobs"]["repeat"] = repeat
    (bench / "configs" / "warm-ec-read-small.json").write_text(
        json.dumps(POOL_CONFIG))
    (bench / "traffic" / "read-pool.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "pool_lost_bytes.py").write_text(POOL_METRIC)
    man["configs"].append({
        "name": "warm-ec-read-small", "source": POOL_CONFIG["source"],
        "file": "benchmark/configs/warm-ec-read-small.json",
        "reduced": ["volume_bytes"], "why": "a test"})
    man["workloads"].append({
        "name": "read-pool-small", "config": "warm-ec-read-small",
        "traffic": "read-pool", "chips": 1, "why": "a test"})
    for m in man["end_to_end"]:
        if m["name"] == "req_per_s":
            m["workloads"].append("read-pool-small")
    man["per_layer"].append({
        "name": "pool_lost_bytes", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "EC file pipeline",
        "moves": "req_per_s", "workloads": ["read-pool-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    rc, out, err = rehearse("run.py", "read-pool-small", 2**31 + 41,
                            trace=1, root=str(root), seconds=6)
    assert rc == 0, err[-3000:]
    res = rehearsal_result(out)
    c = res["compared"]
    assert res["correct"] is True and res["failed"] == 0
    # Sixteen clients send.  Here a read of a damaged needle compiles
    # for seconds (interpret mode, a program a width), so on a loaded
    # machine a client that draws two in a row can spend a short window
    # in them and count as idle: most of the sixteen answer inside it.
    assert 8 <= res["seen"]["clients_active"] <= 16
    assert c["requests_failed"] == [0, 0] and c["answers_differ"] == [0, 0]
    assert c["pool_reads_on_lost_shards"][0] > 0
    assert c["pool_reads_on_lost_shards"][1] is None
    assert c["lost_shards_back"] == [0, 0]
    lost = res["metrics"]["pool_lost_bytes"]["value"]
    assert 0 < lost < res["attempted"] * 2**20
    if repeat:
        assert c["rebuilt_files_differ"] == [0, 0]
        assert c["volumes_compared"] == [1, None]
        assert res["seen"]["rebuild_MBps"] > 0
    else:
        assert res["attempted"] > 16
        assert "files_missing" not in c and "rebuild_MBps" not in res["seen"]
        rc, out, err = rehearse("control.py", "read-pool-small", 2**31 + 42,
                                root=str(root), seconds=6)
        assert rc == 0, (out[-2000:], err[-3000:])
        assert "control: shard 10 of volume 102" in out
        c = rehearsal_result(out)["compared"]
        assert c["requests_failed"][0] + c["answers_differ"][0] > 0
        assert c["lost_shards_back"] == [0, 0]
    _nothing_edited(root, before)
