"""Admin jobs on erasure-coded volumes: the volumes a window needs, made
in set-up; the window itself, one `ec.encode` or `ec.rebuild` after
another through the admin shell; and the comparison of what the window
wrote with the plain reference (ecref.py).

Volumes come to exist cheaply: ONE template volume is filled through
HTTP puts from the seed; every further volume is a hard link to the
template's `.dat` (or, sealed, to its shard files) with copies of the
small sidecars, mounted through the server's own `/admin/mount` /
`/admin/ec/mount`.  Nothing writes to a quiet volume's `.dat`, and the
comparison would show it if something did.
"""

from __future__ import annotations

import os
import shutil
import time
from dataclasses import dataclass, field

import numpy as np

from . import ecref
from .data import Http, needle_payload, needle_sizes, put_needles
from .machine import MIB, check
from .served import Server, call

ROW_BYTES = ecref.DATA_SHARDS * ecref.BLOCK          # 10 MiB of volume
CHUNK_ROWS = 4            # the encoder codes (10, 4 MiB) chunks
# A volume's needles stop this far under its nominal size: the `.dat`
# (needles, 40-odd bytes of record around each, superblock) then ends in
# the last row of the last chunk and every coder call has one shape.
FILL_SLACK = 3 * MIB


@dataclass
class Volume:
    """One filled volume and what is known of it."""
    vid: int
    collection: str
    stream: int
    base: str                    # <dir>/<collection>_<vid>
    dat_bytes: int
    kept_dat: str                # a link to the bytes, kept past the seal
    kept_idx: str                # a copy of its index, kept past the seal
    needles: list = field(default_factory=list)   # (key+cookie, i, size)
    shard_dir: str = ""          # where a sealed template's shards are kept


def fill_volume(srv: Server, seed: int, stream: int, collection: str,
                nominal: int, needle_range: tuple[int, int]) -> Volume:
    """One volume of `nominal` bytes (a multiple of 40 MiB), filled
    through puts."""
    check(nominal % (CHUNK_ROWS * ROW_BYTES) == 0,
          f"volume of {nominal} bytes is no whole number of chunks")
    call(f"{srv.master}/vol/grow?count=1&collection={collection}", {})
    lo, hi = needle_range
    sizes = needle_sizes(seed, lo, min(hi, nominal // 8),
                         nominal - FILL_SLACK)
    put = put_needles(srv.master, collection, seed, stream, sizes)
    vids = {int(fid.split(",")[0]) for fid, _u, _i, _s in put}
    check(len(vids) == 1, f"{collection}: uploads landed in {vids}")
    vid = vids.pop()
    base = os.path.join(srv.data_dir, f"{collection}_{vid}")
    dat_bytes = os.path.getsize(base + ".dat")
    check(ecref.shard_size(dat_bytes) * ecref.DATA_SHARDS == nominal,
          f"{collection}: .dat of {dat_bytes} bytes does not end in the "
          f"last row of a {nominal}-byte volume")
    keep = os.path.join(srv.work, "keep")
    os.makedirs(keep, exist_ok=True)
    kept = os.path.join(keep, f"{collection}.dat")
    os.link(base + ".dat", kept)
    kept_idx = os.path.join(keep, f"{collection}.idx")
    shutil.copyfile(base + ".idx", kept_idx)
    return Volume(vid, collection, stream, base, dat_bytes, kept, kept_idx,
                  [(fid.split(",")[1], i, s) for fid, _u, i, s in put])


def needle_records(tpl: Volume) -> list[tuple[int, int]]:
    """(offset, bytes) in the volume of every needle's record, in the
    order of `tpl.needles`: from the index kept when the volume was
    filled and the format's own arithmetic (ecref.py), each held
    against the header the kept bytes have there."""
    with open(tpl.kept_dat, "rb") as dat:
        version = dat.read(1)[0]
        check(version == ecref.RECORD_VERSION,
              f"{tpl.collection}: a version {version} volume")
        index = ecref.index_entries(tpl.kept_idx)
        out = []
        for key_cookie, _i, size in tpl.needles:
            key, cookie = int(key_cookie[:-8], 16), int(key_cookie[-8:], 16)
            check(key in index, f"{tpl.collection}: needle {key:x} is "
                                f"not in the index")
            offset, body = index[key]
            dat.seek(offset)
            head = dat.read(ecref.RECORD_HEADER.size + 4)
            check(ecref.RECORD_HEADER.unpack_from(head) == (cookie, key,
                                                            body)
                  and int.from_bytes(head[-4:], "big") == size,
                  f"{tpl.collection}: no record of needle {key:x} with "
                  f"{size} bytes at {offset}")
            out.append((offset, ecref.record_bytes(body)))
    # The records lie end to end and the last one ends the volume: the
    # arithmetic of a record's length, held against the file.
    ends = sorted((o + n, o) for o, n in out)
    check(all(a[0] == b[1] for a, b in zip(ends, ends[1:]))
          and ends[-1][0] == tpl.dat_bytes,
          f"{tpl.collection}: the records do not lie end to end up to "
          f"the volume's {tpl.dat_bytes} bytes")
    return out


def clone_unsealed(srv: Server, tpl: Volume, vids: list[int]) -> None:
    """Further quiet volumes with the template's bytes, loaded by the
    server's own mount."""
    for vid in vids:
        base = os.path.join(srv.data_dir, f"{tpl.collection}_{vid}")
        os.link(tpl.kept_dat, base + ".dat")
        shutil.copyfile(tpl.base + ".idx", base + ".idx")
        call(f"{srv.volume}/admin/mount", {"volume": vid})


def keep_shards(srv: Server, tpl: Volume) -> None:
    """Links to a sealed template's 14 shards, kept past any loss."""
    tpl.shard_dir = os.path.join(srv.work, "keep", tpl.collection)
    os.makedirs(tpl.shard_dir, exist_ok=True)
    for sid in range(ecref.TOTAL_SHARDS):
        os.link(tpl.base + ecref.ext(sid),
                os.path.join(tpl.shard_dir, "shard" + ecref.ext(sid)))


def clone_sealed(srv: Server, tpl: Volume, vids: list[int],
                 lost: list[int]) -> None:
    """Further sealed volumes with the template's shards, mounted by the
    server, then `lost` deleted from each the way a disk loses them."""
    for vid in vids:
        base = os.path.join(srv.data_dir, f"{tpl.collection}_{vid}")
        for sid in range(ecref.TOTAL_SHARDS):
            os.link(os.path.join(tpl.shard_dir, "shard" + ecref.ext(sid)),
                    base + ecref.ext(sid))
        for side in (".ecx", ".ecc", ".vif", ".ecj"):
            if os.path.exists(tpl.base + side):
                shutil.copyfile(tpl.base + side, base + side)
        call(f"{srv.volume}/admin/ec/mount", {"volume": vid})
    lose(srv, vids, lost)


def lose(srv: Server, vids: list[int], lost: list[int]) -> None:
    for vid in vids:
        call(f"{srv.volume}/admin/ec/delete_shards",
             {"volume": vid, "shards": lost})


def run_job(srv: Server, op: str, vid: int) -> None:
    """One admin job through the shell.  `op` is the shell command."""
    out = srv.shell(f"{op} -volumeId {vid}")
    want = {"ec.encode": f"volume {vid} -> ec shards",
            "ec.rebuild": f"volume {vid}: rebuilt shards"}[op]
    check(want in out, f"{op} of volume {vid}: {out!r}")


def jobs_window(srv: Server, op: str, vids: list[int],
                volume_bytes: int) -> dict:
    """One job after another over the whole pool: a fixed amount of
    work.  All bytes over all the time from the first job's start to
    the last job's end."""
    done = []
    t_open = time.perf_counter()
    for vid in vids:
        t0 = time.perf_counter()
        run_job(srv, op, vid)
        done.append((vid, t0 - t_open, time.perf_counter() - t_open))
    window = done[-1][2]
    return {"jobs": done, "window_s": window,
            "bytes": volume_bytes * len(done),
            "MBps": volume_bytes * len(done) / 1e6 / window}


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------

def sample_rows(seed: int, vid: int, nrows: int, k: int) -> list[int]:
    """The first row, the last, and `k` drawn from the seed."""
    rng = np.random.default_rng([seed, 77, vid])
    return sorted({0, nrows - 1,
                   *(int(r) for r in rng.integers(0, nrows, k))})


def sample_needles(seed: int, vid: int, needles: list, k: int) -> list:
    """`k` needles drawn from the seed, the longest among them."""
    rng = np.random.default_rng([seed, 78, vid])
    idx = {int(j) for j in rng.choice(len(needles), min(k, len(needles)),
                                      replace=False)}
    idx.add(max(range(len(needles)), key=lambda j: needles[j][2]))
    return [needles[j] for j in sorted(idx)]


def files_missing(base: str, tpl: Volume) -> int:
    """Shard files and sidecars of a sealed volume that are not there,
    or not of the size the volume's bytes give."""
    size = ecref.shard_size(tpl.dat_bytes)
    shards = [base + ecref.ext(sid) for sid in range(ecref.TOTAL_SHARDS)]
    return sum(not os.path.exists(p) or os.path.getsize(p) != size
               for p in shards) + \
        sum(not os.path.exists(base + side) for side in (".ecx", ".ecc"))


def compare_shards(base: str, tpl: Volume, seed: int, vid: int,
                   rows_k: int, shards: list[int] | None = None) -> dict:
    """Counts of what differs from the reference in one sealed volume
    (each has the limit 0): files missing or of the wrong size; on
    sampled rows, data blocks that are not the volume's bytes and
    parity blocks that are not the reference's parity of them; on every
    block of `shards` (all 14 by default), `.ecc` entries that are not
    the block's crc32c."""
    out = {"files_missing": files_missing(base, tpl),
           "data_blocks_differ": 0, "parity_blocks_differ": 0,
           "ecc_entries_differ": 0}
    if out["files_missing"]:
        return out
    size = ecref.shard_size(tpl.dat_bytes)
    paths = {sid: base + ecref.ext(sid)
             for sid in range(ecref.TOTAL_SHARDS)}
    for row in sample_rows(seed, vid, size // ecref.BLOCK, rows_k):
        data = ecref.dat_row(tpl.kept_dat, row)
        want = np.concatenate([data, ecref.encode(data)])
        for sid in range(ecref.TOTAL_SHARDS):
            if not np.array_equal(ecref.read_block(paths[sid], row),
                                  want[sid]):
                out["data_blocks_differ" if sid < ecref.DATA_SHARDS
                    else "parity_blocks_differ"] += 1
    ecc = ecref.load_ecc(base)
    for sid in (range(ecref.TOTAL_SHARDS) if shards is None else shards):
        got, want_crcs = ecc.get(sid), ecref.file_block_crcs(paths[sid])
        if got is None or len(got) != len(want_crcs):
            out["ecc_entries_differ"] += len(want_crcs)
        else:
            out["ecc_entries_differ"] += sum(
                a != b for a, b in zip(got, want_crcs))
    return out


def files_differ(a: str, b: str) -> int:
    """1 where two files differ in size or in any byte."""
    if os.path.getsize(a) != os.path.getsize(b):
        return 1
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while chunk := fa.read(8 * MIB):
            if chunk != fb.read(len(chunk)):
                return 1
    return 0


def compare_needles(srv: Server, tpl: Volume, seed: int, vid: int,
                    k: int) -> dict:
    """Needles read back through the sealed volume that are not the
    bytes the seed gives."""
    http = Http(srv.master)
    bad = 0
    picked = sample_needles(seed, vid, tpl.needles, k)
    try:
        for needle in picked:
            got = http.read(f"127.0.0.1:{srv.vport}", f"{vid},{needle[0]}")
            bad += got != needle_payload(seed, tpl.stream, needle[1],
                                         needle[2])
    finally:
        http.close()
    return {"needles_differ": bad, "needles_read": len(picked)}


def add(total: dict, part: dict) -> None:
    for k, v in part.items():
        total[k] = total.get(k, 0) + v
