"""Pallas coder: the least time a chip could take over the window's
lost bytes — every byte of an answered needle's record on a lost data
shard (`pool_lost_bytes`, the harness's own layout), ten survivor rows
in and one row out (`work.lost_read_bytes` / `lost_read_ops`: the same
work whatever launches it and however wide it pads) — over the summed
device time of the kernel's events in the trace.

What the number is NOT.  The work is the WINDOW's; the kernel seconds
are the whole TRACE's, the traffic file's `warm_seconds` of reads before
the window included (3 s beside 10: the share reads low by about
3 / 13, and by another share with another `warm_seconds`).  And the
work is ten rows in and one out of the interval's own width, while the
program the kernel ran is padded to a width of the list and to four
rows out.  Both read the share low, never high.  It is a reading of one
traffic file, to be held beside itself only: no claim rests on it, and
none may until the harness cuts the trace where the window opens (the
facts give a reader neither the lead's reads nor the kernel's events
by time: PERF.md section 7)."""

from benchmark import tracing, work

KERNEL = "apply_bitmatrix"
K = 10


def read(facts):
    req, trace = facts["requests"], facts["trace"]
    if not req or not req.get("pool_lost_bytes") or not trace:
        return None
    took = tracing.kernel_seconds(trace["ops"], KERNEL)
    if not took:
        return None
    return 100.0 * work.least_seconds(
        K, 1, req["pool_lost_bytes"], facts["device_kind"])["seconds"] / took
