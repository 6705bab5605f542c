"""The traced rehearsals of the two job cells report every stage share
the manifest lists for them, and the main-thread shares with the
unspanned share are the whole window.  On the CPU platform at a tiny
size (Pallas in interpret mode), a real `server` process each: kept in
ONE file of their own, so that one worker runs them one after another
(tests/bench/test_bench_rehearsals.py says why)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from _bench_util import ROOT, rehearsal_result, rehearse  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MAN = json.load(_f)


@pytest.mark.parametrize("cell", ["seal", "rebuild"])
def test_traced_rehearsal_reports_every_stage_share(cell):
    listed = {m["name"] for m in MAN["per_layer"]
              if m["source"] == "program_span" and cell in m["workloads"]}
    assert len(listed) == {"seal": 7, "rebuild": 6}[cell]
    rc, out, err = rehearse("run.py", cell, 2**31 + 25, trace=1)
    assert rc == 0, err[-3000:]
    res = rehearsal_result(out)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()
           if k in listed}
    assert set(got) == listed
    assert all(v["unit"] == "%" for k, v in res["metrics"].items()
               if k in listed)
    assert all(0.0 <= v <= 100.0 for v in got.values()), got
    main = sum(v for k, v in got.items() if k != "seal_stack_busy_share")
    assert main == pytest.approx(100.0, abs=1e-6), got
