"""EcVolume: runtime access to an erasure-coded volume's local shards.

Port of the read path in weed/storage/erasure_coding/ec_volume.go and
store_ec.go: binary-search the `.ecx` for the needle, map its byte range to
shard intervals, read each interval from a local shard — and when a shard
is missing, reconstruct exactly that interval from >= 10 surviving shards
(the degraded-read path that the TPU batches into one GF matmul).

In the clustered setting the "fetch other shards" step goes over the wire
(cluster layer); here the EcVolume handles whatever shards are local and
exposes the same reconstruction hook.
"""

from __future__ import annotations

import functools
import mmap
import os
import threading

import numpy as np

from . import DATA_SHARDS, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, to_ext
from ..codecs import get_codec
from ..core import types as t
from ..core.needle import Needle, get_actual_size
from ..ops.erasure import ErasureCoder, new_coder
from ..stats.metrics import ec_repair_read_bytes_total
from .locate import Interval, locate_data
from .volume_info import ec_codec_name


class NeedleNotFound(Exception):
    pass


class ShardsUnavailable(Exception):
    pass


class EcVolumeShard:
    """One local `.ec??` file."""

    def __init__(self, base_file_name: str, shard_id: int):
        self.shard_id = shard_id
        self.path = base_file_name + to_ext(shard_id)
        self._f = open(self.path, "rb")
        self.size = os.path.getsize(self.path)

    def read_at(self, offset: int, size: int) -> bytes:
        return os.pread(self._f.fileno(), size, offset)

    def fileno(self) -> int:
        return self._f.fileno()

    def read_into(self, buf, offset: int) -> int:
        """Fill the writable buffer `buf` from `offset`, in place;
        the bytes read (short at the file's end)."""
        return os.preadv(self._f.fileno(), [buf], offset)

    def close(self) -> None:
        self._f.close()


@functools.lru_cache(maxsize=1)
def _pread_rows():
    """utils/native.py `pread_rows`, or None where the host library is
    not built (or an older one was named by hand)."""
    from ..utils import native
    lib = native.load()
    if lib is None or not hasattr(lib, "sw_pread_rows"):
        return None
    return native.pread_rows_fn(lib)


def read_many_path() -> str:
    """The way `read_many` reads in this process: `sw_pread_rows` (one
    call of the host library a GET) or `preadv` (a call a row, where
    the library is not built).  A server logs it once at its start and
    `/debug/device` shows it as `ec_reads`: the second way costs a
    degraded GET beside fifteen others twenty times the first's gather
    (`read_many`'s readings)."""
    return "sw_pread_rows" if _pread_rows() is not None else "preadv"


def read_many(reads: list[tuple[EcVolumeShard, int, np.ndarray]]
              ) -> list[bool]:
    """Fill every `row` of `reads` — (shard, offset, row), `row` a
    writable uint8 vector — from its shard's file at its offset; True
    where a read came in full.  The reads of one GET go out in ONE call
    of the host library where it is built: the interpreter's lock is
    dropped and retaken once, not once a read, and beside sixteen busy
    request threads each retake waits its turn (PERF.md section 6,
    PR 36: ten `preadv` calls of a degraded read's gather took 71-73 ms
    there, a needle's own five intervals the most of a healthy read's
    14).  Without the library, or for one read, `preadv` a row."""
    batch = _pread_rows() if len(reads) > 1 else None
    if batch is None:
        return [sh.read_into(row, off) == row.nbytes
                for sh, off, row in reads]
    got = batch([sh.fileno() for sh, _o, _r in reads],
                [off for _s, off, _r in reads],
                [row for _s, _o, row in reads])
    return [n == row.nbytes for n, (_s, _o, row) in zip(got, reads)]


class EcVolume:
    def __init__(self, base_file_name: str, vid: int = 0,
                 coder: ErasureCoder | None = None,
                 version: int | None = None,
                 large_block_size: int = LARGE_BLOCK_SIZE,
                 small_block_size: int = SMALL_BLOCK_SIZE,
                 codec=None):
        self.base_file_name = base_file_name
        self.vid = vid
        self.large_block_size = large_block_size
        self.small_block_size = small_block_size
        # The codec rides the .vif sidecar (like the needle version):
        # an explicit coder wins, then an explicit codec name, then
        # whatever the shards were generated with.
        self._coder = coder
        if coder is not None:
            self.codec = getattr(coder, "codec", None) or get_codec("rs")
        else:
            self.codec = get_codec(codec or ec_codec_name(base_file_name))
        self.shards: dict[int, EcVolumeShard] = {}
        self._ecx = open(base_file_name + ".ecx", "r+b")
        self.ecx_size = os.path.getsize(base_file_name + ".ecx")
        # The sorted index is searched, and a tombstone written, through
        # one shared mapping of the file (upstream searches by pread): a
        # lookup then drops the interpreter's lock for no read, where
        # ten preads a GET each waited their turn to retake it beside
        # sixteen busy request threads.  An empty index maps nothing
        # and is never searched.
        self._index = mmap.mmap(self._ecx.fileno(), self.ecx_size) \
            if self.ecx_size else None
        self._ecj_lock = threading.Lock()
        self.load_local_shards()
        # Version detection is lazy: a server holding only parity shards
        # can still mount and serve raw shard bytes without knowing it.
        self._version = version

    @property
    def coder(self) -> ErasureCoder:
        """Built on first reconstruct, not at mount: resolving the
        default backend claims the device (utils/jaxenv.py), and a
        server that only stores and serves shards never needs one."""
        if self._coder is None:
            self._coder = new_coder(codec=self.codec)
        return self._coder

    @property
    def version(self) -> int:
        if self._version is None:
            self._version = self._detect_version()
        return self._version

    def _detect_version(self) -> int:
        """Volume version: .vif sidecar, else shard 0's superblock, else
        reconstruct the superblock bytes from >=10 survivors.

        A wrong version mis-sizes every record, so no silent default.
        """
        from ..core.super_block import SuperBlock
        from .decoder import read_ec_volume_version
        from .volume_info import load_volume_info
        info = load_volume_info(self.base_file_name)
        if info and "version" in info:
            return int(info["version"])
        try:
            return read_ec_volume_version(self.base_file_name)
        except FileNotFoundError:
            pass
        head = self._reconstruct_interval(0, 0, 64)
        return SuperBlock.from_bytes(head).version

    # -- shard registry ----------------------------------------------------

    def load_local_shards(self) -> list[int]:
        found = []
        for sid in range(self.codec.total_shards):
            if sid in self.shards:
                continue
            if os.path.exists(self.base_file_name + to_ext(sid)):
                self.shards[sid] = EcVolumeShard(self.base_file_name, sid)
                found.append(sid)
        return found

    def shard_size(self) -> int:
        if not self.shards:
            return 0
        return next(iter(self.shards.values())).size

    # -- .ecx search --------------------------------------------------------

    def find_needle_from_ecx(self, needle_id: int) -> tuple[int, int]:
        """Binary search the sorted index. Returns (offset, size)."""
        entry, _pos = self._search_ecx(needle_id)
        if entry is None:
            raise NeedleNotFound(f"needle {needle_id:x} not in ecx")
        if t.size_is_deleted(entry.size):
            raise NeedleNotFound(f"needle {needle_id:x} deleted")
        return entry.offset, entry.size

    def _search_ecx(self, needle_id: int):
        lo, hi = 0, self.ecx_size // t.NEEDLE_MAP_ENTRY_SIZE
        index = self._index
        while lo < hi:
            mid = (lo + hi) // 2
            e = t.NeedleMapEntry.from_bytes(
                index[mid * t.NEEDLE_MAP_ENTRY_SIZE:
                      (mid + 1) * t.NEEDLE_MAP_ENTRY_SIZE])
            if e.key == needle_id:
                return e, mid
            if e.key < needle_id:
                lo = mid + 1
            else:
                hi = mid
        return None, -1

    # -- reads ---------------------------------------------------------------

    def locate_needle(self, needle_id: int) -> tuple[int, int, list[Interval]]:
        offset, size = self.find_needle_from_ecx(needle_id)
        total = get_actual_size(size, self.version)
        dat_size = DATA_SHARDS * self.shard_size()
        intervals = locate_data(self.large_block_size, self.small_block_size,
                                dat_size, offset, total)
        return offset, size, intervals

    def read_interval(self, interval: Interval) -> bytes:
        sid, off = interval.to_shard_id_and_offset(self.large_block_size,
                                                   self.small_block_size)
        shard = self.shards.get(sid)
        if shard is not None:
            buf = shard.read_at(off, interval.size)
            if len(buf) == interval.size:
                return buf
        return self._reconstruct_interval(sid, off, interval.size)

    def _reconstruct_interval(self, missing_sid: int, offset: int,
                              size: int) -> bytes:
        """Degraded read: rebuild one shard interval from survivors.

        Reference: store_ec.go:322 recoverOneRemoteEcShardInterval — there
        the survivors are fetched over gRPC; locally we use whatever shard
        files exist.  The read set follows the codec's repair plan —
        local group first (5 reads for LRC), global fallback — and a
        shard that comes up short is excluded and the plan re-solved,
        so one truncated file degrades the read cost, never the read.
        """
        excluded: set[int] = set()
        while True:
            usable = tuple(s for s in self.shards
                           if s != missing_sid and s not in excluded)
            try:
                plan = self.codec.repair_plan(usable, [missing_sid])
            except ValueError:
                raise ShardsUnavailable(
                    f"cannot reconstruct shard {missing_sid}: only "
                    f"{len(usable)} survivors") from None
            have: dict[int, np.ndarray] = {}
            for sid in plan[0].reads:
                buf = self.shards[sid].read_at(offset, size)
                if len(buf) != size:
                    excluded.add(sid)
                    break
                have[sid] = np.frombuffer(buf, dtype=np.uint8)
            if len(have) == len(plan[0].reads):
                break
        ec_repair_read_bytes_total.inc(size * len(have),
                                       codec=self.codec.name)
        rec = self.coder.reconstruct(have, wanted=[missing_sid])
        return np.asarray(rec[missing_sid]).tobytes()

    def read_needle(self, needle_id: int) -> Needle:
        _offset, size, intervals = self.locate_needle(needle_id)
        blob = b"".join(self.read_interval(iv) for iv in intervals)
        return Needle.from_bytes(blob, self.version)

    # -- deletes -------------------------------------------------------------

    def delete_needle(self, needle_id: int) -> None:
        """Tombstone the .ecx entry in place + append id to the .ecj."""
        entry, pos = self._search_ecx(needle_id)
        if entry is None:
            return
        size_off = (pos * t.NEEDLE_MAP_ENTRY_SIZE + t.NEEDLE_ID_SIZE +
                    t.OFFSET_SIZE)
        tombstone = t.size_to_bytes(t.TOMBSTONE_FILE_SIZE)
        self._index[size_off:size_off + len(tombstone)] = tombstone
        with self._ecj_lock:
            with open(self.base_file_name + ".ecj", "ab") as f:
                f.write(t.put_uint64(needle_id))

    def close(self) -> None:
        if self._index is not None:
            self._index.close()
        self._ecx.close()
        for s in self.shards.values():
            s.close()
        self.shards.clear()
