"""Pallas coder: the least time a chip could take over the window's
reconstruct calls — 10 surviving rows in, the lost rows out, n the shard
bytes of every volume rebuilt — over the summed device time of the
kernel's events in the trace."""

from benchmark import tracing, work

KERNEL = "apply_bitmatrix"
OP, K = "ec.rebuild", 10


def read(facts):
    jobs, trace = facts["jobs"], facts["trace"]
    if not jobs or jobs["op"] != OP or not trace:
        return None
    took = tracing.kernel_seconds(trace["ops"], KERNEL)
    if not took:
        return None
    n = jobs["count"] * jobs["shard_bytes"]
    return 100.0 * work.least_seconds(
        K, jobs["lost"], n, facts["device_kind"])["seconds"] / took
