"""What a coder call HAS to do, whatever implements it, and the least
time a chip could take over it.

For `(k, n)` bytes in and `r` rows of `n` bytes out, a GF(2^8) code
moves `(k + r) * n` bytes and, as the bit-matrix product it is on a
matrix unit, makes `2 * 8r * 8k * n` integer operations.  A fused CRC, a
wider tile or another dtype changes the kernel's time and not this
count.  A read that meets lost bytes is the same code with one row out:
an interval of `n` lost bytes moves `(k + 1) * n` bytes and makes
`2 * 8 * 8k * n` operations, whatever launches it and however many
intervals a launch holds (`facts["requests"]["pool_lost_bytes"]` is that
`n` summed over a window's reads).  Peaks come from one table,
peaks.json, keyed by `device_kind`;
a device that is not in it is an error, never a default.
"""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; the table has {sorted(table)}")
    return table[device_kind]


def coder_bytes(k: int, r: int, n: int) -> int:
    return (k + r) * n


def coder_ops(k: int, r: int, n: int) -> int:
    return 2 * (8 * r) * (8 * k) * n


def lost_read_bytes(k: int, n: int) -> int:
    return coder_bytes(k, 1, n)


def lost_read_ops(k: int, n: int) -> int:
    return coder_ops(k, 1, n)


def least_seconds(k: int, r: int, n: int, device_kind: str) -> dict:
    """The larger of bytes over the memory peak and operations over the
    int8 peak, and which of the two it is."""
    p = peaks(device_kind)
    by_bytes = coder_bytes(k, r, n) / p["hbm_bytes_per_s"]
    by_ops = coder_ops(k, r, n) / p["int8_ops"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "memory" if by_bytes >= by_ops else "compute"}
