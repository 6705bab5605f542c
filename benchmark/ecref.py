"""The plain reference of `warm-ec-rs10-4`: what a sealed volume's files
must hold, worked out with table look-ups in numpy.

Imports nothing of the program and takes nothing the program made.  The
code is klauspost/reedsolomon's as SeaweedFS uses it (`reedsolomon.New(10,
4)`): GF(2^8) over x^8+x^4+x^3+x^2+1, an extended Vandermonde matrix made
systematic by the inverse of its top square.  A shard file is the
volume's `.dat` cut into rows of ten 1 MiB blocks, block i of every row
going to shard i, the last row zero-filled (volumes under 10 GB have no
1 GB large-block rows).  `.ecc` holds one crc32c per 1 MiB block of each
shard, as JSON hex strings.

Where a needle lies is the format's too (SeaweedFS `weed/storage/types`
and `needle`): an index (`.idx`, or a sealed volume's sorted `.ecx`) is
16-byte entries, big-endian: the key (8), the record's offset in units of
8 bytes (4), the needle's Size (4, signed); a version 3 record is a
16-byte header, Size bytes, a 4-byte checksum, an 8-byte timestamp and 1
to 8 bytes of padding to the next multiple of 8.  `lost_bytes` says how
much of a record lies on given data shards: what any implementation has
to reconstruct to give the needle back once those shards are gone.
"""

from __future__ import annotations

import json
import struct

import numpy as np

try:
    import google_crc32c
except ImportError:                      # pragma: no cover - same install
    google_crc32c = None

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS
BLOCK = 1 << 20
POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    return exp, log


_EXP, _LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    return int(_EXP[(255 - _LOG[a]) % 255])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    return 0 if a == 0 else int(_EXP[(_LOG[a] * n) % 255])


MUL = np.array([[gf_mul(a, b) for b in range(256)] for a in range(256)],
               np.uint8)


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for l, v in enumerate(row):
                acc ^= gf_mul(v, b[l][j])
            out[i][j] = acc
    return out


def mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    w = [list(r) + [int(i == j) for j in range(n)] for i, r in enumerate(m)]
    for c in range(n):
        p = next(r for r in range(c, n) if w[r][c])
        w[c], w[p] = w[p], w[c]
        inv = gf_inv(w[c][c])
        w[c] = [gf_mul(inv, v) for v in w[c]]
        for r in range(n):
            if r != c and w[r][c]:
                f = w[r][c]
                w[r] = [a ^ gf_mul(f, b) for a, b in zip(w[r], w[c])]
    return [r[n:] for r in w]


def code_matrix(k: int = DATA_SHARDS, total: int = TOTAL_SHARDS):
    """(total x k): identity on top, the parity rows below."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(total)]
    return mat_mul(vm, mat_inv(vm[:k]))


def apply_rows(rows: list[list[int]], shards: np.ndarray) -> np.ndarray:
    """out[r] = XOR_c rows[r][c] * shards[c] over GF(2^8)."""
    out = np.zeros((len(rows), shards.shape[1]), np.uint8)
    for r, row in enumerate(rows):
        for c, coef in enumerate(row):
            if coef:
                out[r] ^= MUL[coef][shards[c]]
    return out


def encode(data: np.ndarray, broken: bool = False) -> np.ndarray:
    """(10, n) data -> (4, n) parity.  `broken` is the control: the last
    parity row is the plain XOR of the data rows (a RAID-5 row where the
    fourth Reed-Solomon row belongs), so some losses of four shards can
    no longer be decoded — cheaper to compute, and not RS(10,4)."""
    rows = code_matrix()[DATA_SHARDS:]
    if broken:
        rows = rows[:-1] + [[1] * DATA_SHARDS]
    return apply_rows(rows, np.ascontiguousarray(data, np.uint8))


def reconstruct(have: dict[int, np.ndarray], wanted: list[int]):
    """Any ten shards give back the others: {id: row} -> {wanted id: row}."""
    used = sorted(have)[:DATA_SHARDS]
    full = code_matrix()
    dec = mat_inv([full[s] for s in used])           # survivors -> data
    rows = [mat_mul([full[w]], dec)[0] for w in wanted]
    out = apply_rows(rows, np.stack([have[s] for s in used]))
    return {w: out[i] for i, w in enumerate(wanted)}


def _crc_table() -> list[int]:
    t = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (0x82F63B78 if c & 1 else 0)
        t.append(c)
    return t


_CRC_T = _crc_table()


def crc32c_plain(buf: bytes) -> int:
    """Castagnoli CRC, byte by byte: the check on the fast library."""
    c = 0xFFFFFFFF
    for b in buf:
        c = _CRC_T[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def crc32c(buf) -> int:
    if google_crc32c is not None:
        return int(google_crc32c.value(bytes(buf)))
    return crc32c_plain(bytes(buf))


def shard_size(dat_bytes: int) -> int:
    rows = -(-dat_bytes // (DATA_SHARDS * BLOCK))
    return rows * BLOCK


INDEX_ENTRY = struct.Struct(">QIi")       # key, offset / 8, Size
RECORD_HEADER = struct.Struct(">IQi")      # cookie, key, Size
RECORD_VERSION = 3
_RECORD_AROUND = RECORD_HEADER.size + 4 + 8   # header, checksum, timestamp


def index_entries(path: str) -> dict[int, tuple[int, int]]:
    """{key: (the record's offset in the volume, its Size)} of an
    `.idx` or `.ecx`; a later entry of a key replaces an earlier one."""
    with open(path, "rb") as f:
        raw = f.read()
    return {key: (units * 8, size)
            for key, units, size in INDEX_ENTRY.iter_unpack(raw)}


def record_bytes(size: int) -> int:
    """The bytes a version 3 record of Size `size` takes in the volume."""
    return _RECORD_AROUND + size + 8 - (_RECORD_AROUND + size) % 8


def lost_bytes(offset: int, length: int, lost: list[int]) -> int:
    """Of the volume's bytes [offset, offset + length), those that lie
    in a block of a data shard in `lost`: block b of the volume is
    block b // 10 of shard b % 10."""
    gone = {s for s in lost if s < DATA_SHARDS}
    n, end = 0, offset + length
    while offset < end:
        block = offset // BLOCK
        upto = min(end, (block + 1) * BLOCK)
        if block % DATA_SHARDS in gone:
            n += upto - offset
        offset = upto
    return n


def ext(sid: int) -> str:
    return f".ec{sid:02d}"


def read_block(path: str, block: int) -> np.ndarray:
    """Block `block` of a file, zero-filled past its end."""
    with open(path, "rb") as f:
        f.seek(block * BLOCK)
        raw = f.read(BLOCK)
    out = np.zeros(BLOCK, np.uint8)
    out[:len(raw)] = np.frombuffer(raw, np.uint8)
    return out


def dat_row(dat_path: str, row: int) -> np.ndarray:
    """Row `row` of the volume as the (10, 1 MiB) stripe the code sees."""
    return np.stack([read_block(dat_path, row * DATA_SHARDS + i)
                     for i in range(DATA_SHARDS)])


def load_ecc(base: str) -> dict[int, list[int]]:
    with open(base + ".ecc") as f:
        doc = json.load(f)
    if doc.get("block") != BLOCK:
        raise ValueError(f"{base}.ecc: block {doc.get('block')}")
    return {int(s): [int(h, 16) for h in crcs]
            for s, crcs in doc["shards"].items()}


def file_block_crcs(path: str) -> list[int]:
    out = []
    with open(path, "rb") as f:
        while buf := f.read(BLOCK):
            out.append(crc32c(buf))
    return out
