"""The readers of the EC file pipeline's stage rows (benchmark/stages.py
and the `*_share` files of benchmark/metrics/), on facts made by hand:
a share is the rows' seconds over the window, None for the other job
and for a program that serves no such row (every commit before the
stage clock), and the main-thread shares with the unspanned share are
the whole window.  No test here starts a server."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

MAN = manifest.load()

SEAL_ROWS = {"seal.stack_wait": 0.5, "seal.stack": 6.0,
             "seal.dispatch": 2.0, "seal.write_data": 2.5,
             "seal.drain": 1.0, "seal.write_parity": 1.5,
             "seal.finish": 0.25, "seal.mount": 0.5,
             "seal.delete_original": 0.25,
             "encode_crc_kernel": 1.75}
REBUILD_ROWS = {"rebuild.read": 1.0, "rebuild.dispatch": 3.0,
                "rebuild.drain": 2.0, "rebuild.write": 2.5,
                "rebuild.finish": 0.25, "rebuild.mount": 0.25,
                "reconstruct_kernel": 0.5}
SEAL = {"seal_stack_wait_share": 5.0, "seal_stack_busy_share": 60.0,
        "seal_dispatch_share": 20.0, "seal_drain_share": 10.0,
        "seal_write_share": 40.0, "seal_finish_share": 10.0,
        "seal_unspanned_share": 15.0}
REBUILD = {"rebuild_read_share": 10.0, "rebuild_dispatch_share": 30.0,
           "rebuild_drain_share": 20.0, "rebuild_write_share": 25.0,
           "rebuild_finish_share": 5.0, "rebuild_unspanned_share": 10.0}


def _facts(op, rows):
    return {"window_s": 10.0, "requests": None, "trace": None,
            "jobs": {"op": op, "count": 3, "shard_bytes": 1, "lost": 2},
            "coder_rows": {k: {"count": 13, "seconds": s, "bytes": 1}
                           for k, s in rows.items()}}


@pytest.mark.parametrize("name,want", [*SEAL.items(), *REBUILD.items()])
def test_stage_share_readers_on_facts_made_by_hand(name, want):
    read = manifest.reader(MAN, name)
    op, other = ("ec.encode", "ec.rebuild") if name in SEAL else \
        ("ec.rebuild", "ec.encode")
    rows = SEAL_ROWS if name in SEAL else REBUILD_ROWS
    assert read(_facts(op, rows)) == pytest.approx(want)
    # another job's window, even with this job's rows in it
    assert read(_facts(other, {**SEAL_ROWS, **REBUILD_ROWS})) is None
    # a program without the stage clock: kernel rows only
    kernels = {k: s for k, s in rows.items() if "." not in k}
    assert read(_facts(op, kernels)) is None
    assert read(_facts(op, {})) is None
    # no jobs at all (a request cell)
    assert read(dict(_facts(op, rows), jobs=None)) is None
    entry = dict(next(m for m in MAN["per_layer"] if m["name"] == name))
    # its own cell first; a later PR may append cells that serve the rows
    assert entry.pop("workloads")[0] == \
        ("seal" if name in SEAL else "rebuild")
    assert entry == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "EC file pipeline",
        "moves": "seal_MBps" if name in SEAL else "rebuild_MBps"}


@pytest.mark.parametrize("shares", [SEAL, REBUILD])
def test_main_thread_shares_and_the_unspanned_are_the_window(shares):
    op, rows = ("ec.encode", SEAL_ROWS) if shares is SEAL else \
        ("ec.rebuild", REBUILD_ROWS)
    facts = _facts(op, rows)
    total = sum(manifest.reader(MAN, n)(facts) for n in shares
                if n != "seal_stack_busy_share")
    assert total == pytest.approx(100.0)


PR_24 = {"server_cpu_us_per_req": ["bench-write-1k", "seal-under-load",
                                   "rebuild-under-load"],
         "write_p99_ms": ["bench-write-1k", "seal-under-load",
                          "rebuild-under-load"],
         "longest_stall_ms": ["bench-write-1k", "seal-under-load",
                              "rebuild-under-load"],
         "client_cpu_share": ["bench-write-1k", "seal-under-load",
                              "rebuild-under-load"],
         "encode_kernel_roofline": ["seal"],
         "reconstruct_kernel_roofline": ["rebuild"],
         "seal_compiles_in_window": ["seal"],
         "seal_device_idle_share": ["seal"],
         "rebuild_compiles_in_window": ["rebuild"],
         "rebuild_device_idle_share": ["rebuild"],
         "req_compiles_in_window": ["bench-write-1k"],
         "req_device_idle_share": ["bench-write-1k"]}


def test_stage_metrics_are_appended_and_the_old_ones_untouched():
    """PR 24's twelve that remain (its two `*_coder_call_share` fell
    silent and went with PR 34), then PR 25's thirteen, each still there
    with its cells; what later PRs appended is free."""
    listed = {m["name"]: m["workloads"] for m in MAN["per_layer"]}
    names = list(listed)
    assert names[:12] == list(PR_24)
    assert sorted(names[12:25]) == sorted((*SEAL, *REBUILD))
    for name, cells in PR_24.items():
        assert set(cells) <= set(listed[name]), name
    assert "seal_coder_call_share" not in listed
    assert "rebuild_coder_call_share" not in listed
    assert not any("bench-write-1k" in listed[name]
                   for name in (*SEAL, *REBUILD))
