"""From the profiler's `.xplane.pb` to numbers: seconds in which an
operation ran on the device, seconds by operation name, and the longest
idle gaps by what the host was doing in them.

    python tracing.py <trace dir> <out.json>

reads the newest `*.xplane.pb` under the directory with
`jax.profiler.ProfileData` (the harness runs it as a child with
JAX_PLATFORMS=cpu, after the server has gone: the launcher itself never
imports JAX).  `reduce` is plain arithmetic on lists of events and is
what the tests hold to known answers.

What counts as the device: planes named `/device:TPU:<n>`, and on them
the line `XLA Ops` (every operation the device ran; `XLA Modules` and
`Steps` are the same time seen whole).  Busy time is the union of that
line's intervals, averaged over the device planes.  The host is the
plane `/host:CPU`: the TraceMe spans JAX itself records (a jitted call,
a transfer, `np.asarray` of a device array).  The program records no
spans of its own there, so a gap in which the host ran none of JAX is
`host_outside_jax`: stripe stacking, file reads and writes, HTTP.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
OUTSIDE = "host_outside_jax"
TOP = 10
GAPS_ATTRIBUTED = 400        # the longest; the rest are summed as short


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals: list[tuple[float, float]], lo: float,
            hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(raw: str) -> str:
    """The HLO instruction's name out of a device event's text:
    `%fusion.2 = u32[4,14]{...} fusion(...)` -> `fusion.2`."""
    m = re.match(r"%?([A-Za-z0-9_.\-]+)", raw)
    return m.group(1) if m else raw


def stable(name: str) -> str:
    """An event's name with what varies from run to run taken off:
    `fusion.12` -> `fusion`, `PjitFunction(f)` -> `PjitFunction_f_`."""
    name = re.sub(r"\.\d+$", "", name)
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name)[:64]


def reduce(device: dict[str, list[tuple[str, float, float]]],
           host: list[tuple[str, float, float]]) -> dict:
    """`device`: plane -> [(name, start_s, duration_s)] of its XLA Ops
    line; `host`: the same for every host span.  Times in seconds on one
    clock."""
    if not device:
        return {"devices": 0, "busy_s": 0.0, "ops": {}, "gaps": []}
    starts = [s for evs in device.values() for _n, s, _d in evs]
    ends = [s + d for evs in device.values() for _n, s, d in evs]
    starts += [s for _n, s, _d in host]
    ends += [s + d for _n, s, d in host]
    lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    busy, ops = [], {}
    for evs in device.values():
        busy.append(union_length([(s, s + d) for _n, s, d in evs]))
        for n, _s, d in evs:
            ops[n] = ops.get(n, 0.0) + d
    # Idle gaps of the first device, by what the host was in.
    first = device[sorted(device)[0]]
    gaps = sorted(gaps_of([(s, s + d) for _n, s, d in first], lo, hi),
                  key=lambda g: g[0] - g[1])
    by: dict[str, float] = {}
    h_names = [n for n, _s, _d in host]
    h_s = np.array([s for _n, s, _d in host])
    h_e = h_s + np.array([d for _n, _s, d in host]) if host else h_s
    for gs, ge in gaps[:GAPS_ATTRIBUTED]:
        left = ge - gs
        if len(h_s):
            over = np.minimum(h_e, ge) - np.maximum(h_s, gs)
            j = int(np.argmax(over))
            if over[j] > 0:
                name = stable(h_names[j])
                by[name] = by.get(name, 0.0) + float(over[j])
                left -= float(over[j])
        by[OUTSIDE] = by.get(OUTSIDE, 0.0) + left
    short = sum(e - s for s, e in gaps[GAPS_ATTRIBUTED:])
    if short:
        by["short_gaps"] = short
    return {"devices": len(device), "span_s": hi - lo,
            "busy_s": sum(busy) / len(busy), "ops": ops,
            "gaps": sorted(by.items(), key=lambda kv: -kv[1])}


def top_ops(ops: dict[str, float]) -> list[list]:
    merged: dict[str, float] = {}
    for n, d in ops.items():
        merged[stable(n)] = merged.get(stable(n), 0.0) + d
    return [[n, d] for n, d in
            sorted(merged.items(), key=lambda kv: -kv[1])[:TOP]]


def kernel_seconds(ops: dict[str, float], prefix: str) -> float:
    """Summed device time of the operations whose name starts with
    `prefix` (a kernel's name as the program gives it)."""
    return sum(d for n, d in ops.items() if n.startswith(prefix))


def load(path: str) -> tuple[dict, list]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (op_name(e.name), e.start_ns / 1e9,
                         e.duration_ns / 1e9) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                         for e in line.events if e.duration_ns > 0]
    return device, host


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def main(argv: list[str]) -> int:
    device, host = load(newest_xplane(argv[0]))
    out = reduce(device, host)
    out["gaps"] = out["gaps"][:TOP]
    with open(argv[1], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
