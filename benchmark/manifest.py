"""BENCHMARK.json and the files it names.  The harness is driven by this
data: a configuration is `configs/<config>.json` (the manifest's `file`),
a traffic mix `traffic/<traffic>.json`, a per-layer metric the reader
`metrics/<name>.py` with one function `read(facts)`.  A later PR adds
entries and files; nothing here names a cell, a mix or a metric.  A
configuration's `server_env` may set glibc's `MALLOC_*` variables for
its server and nothing else (served.py `server_environment`).

A traffic file holds `why`, `sent_by` and `jobs` and/or `requests`
(run.py's docstring has every key): jobs of one kind over a pool of
`jobs.volumes` volumes, `jobs.repeat` of them in the window (0 with
requests: the pool is only made and damaged); requests that write, read
a key set of their own, or with `keys_from: "pool"` read every needle of
the pool's volumes as the jobs' set-up left them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

from .machine import BenchFailure, check

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(man: dict, workload: str, root: str = ROOT) -> dict:
    """One cell with its configuration and its traffic mix read in."""
    found = [w for w in man["workloads"] if w["name"] == workload]
    check(found, f"no workload {workload!r} in BENCHMARK.json; it has "
                 f"{[w['name'] for w in man['workloads']]}")
    w = found[0]
    cfg = next(c for c in man["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    path = os.path.join(root, man["paths"][0], "traffic",
                        w["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    return {"name": w["name"], "chips": w["chips"], "config": config,
            "traffic": traffic, "traffic_name": w["traffic"]}


def metrics_of(man: dict, workload: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics this cell reports: those
    that list it under `workloads`, and those that list no cells."""
    return [m for m in man[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(man: dict, name: str, root: str = ROOT):
    """`read(facts) -> number or None` of a per-layer metric."""
    path = os.path.join(root, man["paths"][0], "metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"per-layer metric {name!r} has no reader "
                           f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
