"""Pallas coder: the least time a chip could take over the window's
encode calls — (10, n) bytes in, 4 rows out, n the shard bytes of every
volume sealed — over the summed device time of the kernel's events in
the trace.  The kernel's name is the program's."""

from benchmark import tracing, work

KERNEL = "apply_bitmatrix"        # _crc_pallas with the fused CRC, or plain
OP, K, R = "ec.encode", 10, 4


def read(facts):
    jobs, trace = facts["jobs"], facts["trace"]
    if not jobs or jobs["op"] != OP or not trace:
        return None
    took = tracing.kernel_seconds(trace["ops"], KERNEL)
    if not took:
        return None
    n = jobs["count"] * jobs["shard_bytes"]
    return 100.0 * work.least_seconds(
        K, R, n, facts["device_kind"])["seconds"] / took
