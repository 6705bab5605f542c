"""EC encode / rebuild: `.dat` -> `.ec00`-`.ec13`, `.idx` -> `.ecx`.

Behavioral port of weed/storage/erasure_coding/ec_encoder.go with the byte
crunching routed through the pluggable ErasureCoder (numpy / XLA / Pallas
MXU kernel).  Two TPU-minded deviations from the reference's mechanics that
keep outputs byte-identical:

- the reference streams 10 x 256KB buffers per encoder call
  (encodeDataOneBatch); we read much larger contiguous chunks per shard row
  and feed the whole (10, chunk) matrix to one kernel launch — same bytes,
  ~chunk/256KB fewer launches;
- rebuild ignores the block layout entirely: byte column p across shard
  files is one RS codeword, so reconstruction is a flat column-parallel
  matmul over any chunk size.
"""

from __future__ import annotations

import collections
import functools
import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import DATA_SHARDS, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, to_ext
from .integrity import BlockCrcAccumulator, ShardChecksums, ecc_lock
from .volume_info import ec_codec_name, update_volume_info
from ..codecs import get_codec
from ..fault import registry as _fault
from ..ops.erasure import ErasureCoder, new_coder
from ..stats.metrics import ec_repair_read_bytes_total
from ..stats.roofline import StageClock
from ..storage.needle_map import MemDb

# Per-shard contiguous bytes handed to one coder call. Must divide
# LARGE_BLOCK_SIZE and be a multiple of SMALL_BLOCK_SIZE.
DEFAULT_CHUNK = 4 * 1024 * 1024

# Chunks `_pipelined_encode` keeps in flight between dispatch and drain.
# Since its shard writes left the main thread (the writer stage of
# `_run_pipeline`) a turn of the loop is shorter than a chunk's device
# round trip.  The chip's readings (PERF.md section 6, PR 33; MB/s;
# `waited` of 39 drains; `seal.drain` ms a chunk): 2: 1427, 1427;
# 17-20; 10.4-13.5 - 3: 1359-1671 over eight runs; 4-14; 6.7-8.9 - 4:
# 1277-1373; 1-5; 5.3-8.6 - 5: 1184, 1378; 2-5; 3.7-3.9.  Past 3 the
# wait only moves from the drain into the dispatch (the transfers'
# layout passes on the host set the pace, not the round trip's length)
# and every chunk more in flight is 40 MiB more of fresh memory.
SEAL_DEPTH = 3

# Chunks `rebuild_ec_files` keeps in flight: its main thread spends a
# third of a chunk's device round trip per chunk, so the window has to
# be as deep as the round trip is long (PERF.md section 6, PR 31, has
# the chip's readings at 2, 3, 4 and 5).
REBUILD_DEPTH = 4

# Threads that write the seal's fourteen shard files, shard `sid`
# always thread `sid % SEAL_WRITERS`'s.  The chip's readings (PR 33;
# MB/s; hand-overs of 39 that `waited` for the writers): 1: 1141,
# 1300; 18-19 - 2: 1292, 1420; 0-11 - 3: 1359-1671 over eight runs;
# 0-1 - 4: 1431-1602; 0-1 - 5: 1494-1722; 0.  From 3 on the main
# thread never waits for them and the rates do not separate.
SEAL_WRITERS = 3

# Pooled buffers one job has live: its chunks in flight, one read
# ahead of them, one being filled.  The seal's writers hold a chunk's
# buffer until its data rows are in their files, and the main thread
# does not hand them chunk k before chunk k - SEAL_DEPTH is written:
# what they lag stays inside the window.  (A second chunk read ahead
# gave the seal nothing: PR 33, `seal.stack_wait` 0.02-0.41 ms a chunk
# with one, 0.16-0.52 with two.)
SEAL_BUFFERS = SEAL_DEPTH + 2
REBUILD_BUFFERS = REBUILD_DEPTH + 2

# Threads a job's reader spreads the reads of one chunk over: the
# rebuild's ten survivors, the seal's stripe rows (four reads a 40 MiB
# chunk of small blocks).  One thread copies the page cache into a
# pooled buffer at a third of the pace the serial loop read into its
# one warm 4 MiB of heap, and then sets the job's pace; the survivors
# are separate files, on separate disks where a volume server has
# them, so their reads go out side by side (PERF.md section 6, PR 31:
# one thread 17 ms a chunk, five 7 ms beside the pipeline, ten no
# better).  The seal's readings beside its writers and transfers
# (PR 33; ms a chunk; the main thread's wait for the reader): one
# thread 24.0-27.8; 9.2-10.0 - two 18.4-19.7; 1.1-3.0 - five (four at
# work) 13.9-17.2; 0.2-0.5: one constant serves both jobs.
REBUILD_READERS = 5
SEAL_READERS = REBUILD_READERS

# Host buffers of one default chunk (40 MiB) that stay with the process
# between jobs: what the larger of the two jobs has live
# (`_run_pipeline`).  A fresh buffer costs a page fault per 4 KiB on
# first touch — as much as reading the chunk in place saves — so that
# is paid once per process, not once per chunk or per job.
CHUNK_POOL_BUFFERS = max(SEAL_BUFFERS, REBUILD_BUFFERS)


class _ChunkPool:
    """Bounded free list of flat uint8 host buffers of `nbytes` each,
    handed out last-in-first-out so that a short job touches the
    fewest.  A taker that finds it empty allocates; `give` keeps a
    buffer only up to the bound, so concurrent jobs never grow it."""

    def __init__(self, bound: int, nbytes: int):
        self.bound = bound
        self.nbytes = nbytes
        self._lock = threading.Lock()
        self._free: list[np.ndarray] = []
        self._reused = 0
        self._allocated = 0

    def take(self, nbytes: int) -> np.ndarray:
        """A buffer of at least `nbytes`; its contents are garbage."""
        with self._lock:
            if nbytes <= self.nbytes and self._free:
                self._reused += 1
                return self._free.pop()
            self._allocated += 1
        return np.empty(max(nbytes, self.nbytes), dtype=np.uint8)

    def give(self, buf: np.ndarray) -> None:
        """Hand back a buffer nothing reads or writes any more."""
        with self._lock:
            if buf.nbytes == self.nbytes and len(self._free) < self.bound:
                self._free.append(buf)

    def counts(self) -> dict:
        """Chunks built in a reused buffer, chunks a buffer had to be
        allocated for, bytes held for the next job (`/debug/device`)."""
        with self._lock:
            return {"reused": self._reused, "allocated": self._allocated,
                    "held_bytes": len(self._free) * self.nbytes}


CHUNK_POOL = _ChunkPool(CHUNK_POOL_BUFFERS, DATA_SHARDS * DEFAULT_CHUNK)


class _InflightCount:
    """How a job's main thread found what it was about to wait for,
    process-wide, one count per place it may wait: `ready` or `waited`.
    The drains (one count for the seals, one for the rebuilds) note the
    oldest chunk in flight: `ready` says the device was done with it,
    its round trip hid behind the main thread's work, and all `waited`
    that the device path sets the pace.  A device array is ready when
    it is computed; whether the copy back had landed as well is what
    the drain stage's seconds say.  The seal's hand-over notes the
    writer stage: `ready` says the writers were inside the window when
    the main thread had a chunk's data rows for them, and all `waited`
    that the writers set the pace."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ready = 0
        self._waited = 0

    def note(self, ready: bool) -> None:
        with self._lock:
            if ready:
                self._ready += 1
            else:
                self._waited += 1

    def counts(self) -> dict:
        """Chunks so far, by what the main thread found
        (`/debug/device`)."""
        with self._lock:
            return {"ready": self._ready, "waited": self._waited}


SEAL_INFLIGHT = _InflightCount()
REBUILD_INFLIGHT = _InflightCount()
SEAL_WRITER = _InflightCount()


def _request_copy_back(handle) -> None:
    """Start the device -> host copy of a result handle that can copy
    asynchronously (a device array); a host coder's array is here."""
    ask = getattr(handle, "copy_to_host_async", None)
    if ask is not None:
        ask()


def _is_ready(handle) -> bool:
    """Whether `handle` is computed; a host array is."""
    ask = getattr(handle, "is_ready", None)
    return ask is None or bool(ask())


def write_sorted_file_from_idx(base_file_name: str,
                               ext: str = ".ecx") -> None:
    """Generate the sorted `.ecx` from the `.idx` (WriteSortedFileFromIdx).
    Written beside its name and moved over it: a mounted EcVolume maps
    the file it opened (ec/volume.py), and a file cut to nothing under a
    mapping is a SIGBUS at the next lookup, not an error."""
    with open(base_file_name + ".idx", "rb") as f:
        db = MemDb.from_idx(f)
    with open(base_file_name + ext + ".tmp", "wb") as out:
        out.write(db.to_sorted_bytes())
    os.replace(base_file_name + ext + ".tmp", base_file_name + ext)


def _shard_write(f, sid: int, buf, accs) -> None:
    """One shard-file write of `buf` (`bytes`, or a contiguous uint8
    row): feed the integrity accumulator with the TRUE bytes first,
    then write — possibly through the volume.corrupt bit-rot injector —
    so the recorded `.ecc` checksums describe what the encoder intended
    and any on-disk divergence is detectable."""
    if accs is not None:
        accs[sid].feed(buf)
    if _fault.ARMED and len(buf):
        try:
            _fault.hit("volume.corrupt", shard=sid)
        except _fault.FaultInjected:
            b = bytearray(buf)
            b[0] ^= 0xFF
            buf = bytes(b)
    f.write(buf)


def write_ec_files(base_file_name: str, coder: ErasureCoder | None = None,
                   large_block_size: int = LARGE_BLOCK_SIZE,
                   small_block_size: int = SMALL_BLOCK_SIZE,
                   chunk_size: int = DEFAULT_CHUNK,
                   codec=None, clock: StageClock | None = None) -> None:
    """Generate the shard files from the .dat (WriteEcFiles), plus the
    `.ecc` per-block checksum sidecar the background scrub verifies
    shards against (ec/integrity.py).  `codec` selects the erasure
    codec ("rs" default, "lrc", ...); shard-file count, parity rows
    and the recorded `.vif` codec id all derive from it.  `clock` is
    the job's stage clock (stats/roofline.py `seal.*`); a caller that
    wants the job's stage totals passes its own."""
    if coder is None:
        coder = new_coder(codec=codec)
    cd = getattr(coder, "codec", None) or get_codec("rs")
    if codec is not None and get_codec(codec).name != cd.name:
        raise ValueError(
            f"coder carries codec {cd.name!r} but {get_codec(codec).name!r} "
            "was requested")
    if cd.data_shards != DATA_SHARDS:
        # The shard-file block layout (locate.py) row-stripes over
        # exactly DATA_SHARDS columns; codecs may vary parity shape
        # freely but not the data stripe width.
        raise ValueError(
            f"codec {cd.name!r}: data shards must be {DATA_SHARDS} for "
            "the weed shard layout")
    if clock is None:
        clock = StageClock(cd.name)
    dat_size = os.path.getsize(base_file_name + ".dat")
    outputs = [open(base_file_name + to_ext(i), "wb")
               for i in range(cd.total_shards)]
    # Fused path: the device coder emits every shard's per-block
    # CRC32-C alongside the parity (ops/crc_fold.py) — no CPU pass over
    # the shard bytes.  Requires the DEFAULT block geometry: only then
    # is every `_chunk_reader` width a whole number of 1MB `.ecc`
    # blocks, which the kernel demands.  Custom large/small block sizes
    # (or the SEAWEEDFS_TPU_EC_FUSED_CRC=0 kill switch) fall back to the
    # byte accumulators.
    from ..ops.crc_fold import fused_crc_enabled
    fused = (getattr(coder, "fused_crc_ok", False)
             and chunk_size % SMALL_BLOCK_SIZE == 0
             and small_block_size == SMALL_BLOCK_SIZE
             and large_block_size % SMALL_BLOCK_SIZE == 0
             and fused_crc_enabled())
    accs = None if fused \
        else [BlockCrcAccumulator() for _ in range(cd.total_shards)]
    try:
        with open(base_file_name + ".dat", "rb") as dat:
            crc_map = _encode_dat_file(
                dat, dat_size, coder, outputs,
                large_block_size, small_block_size, chunk_size,
                accs=accs, clock=clock)
    finally:
        with clock("seal.finish"):
            for f in outputs:
                f.close()
    with clock("seal.finish"):
        # The codec id travels in the .vif like the needle version: any
        # server that later mounts these shards must pick the matching
        # decode matrices.
        update_volume_info(base_file_name, codec=cd.name)
        with ecc_lock(base_file_name):
            ecc = ShardChecksums(base_file_name)
            for sid in range(cd.total_shards):
                ecc.set_shard(sid, crc_map[sid] if crc_map is not None
                              else accs[sid].finalize())
            ecc.save()


def _encode_dat_file(dat, dat_size: int, coder: ErasureCoder, outputs,
                     large: int, small: int, chunk_size: int,
                     accs=None, clock: StageClock | None = None):
    spans = _chunk_spans(dat_size, large, small, chunk_size)
    return _pipelined_encode(dat.fileno(), spans, coder, outputs,
                             accs=accs, clock=clock)


def _chunk_spans(dat_size: int, large: int, small: int, chunk_size: int):
    """The chunking, in shard-file order and byte-identical to the
    previous serial encoder: yield `(width, reads)` per
    `(DATA_SHARDS, width)` stripe chunk.  A read
    `(offset, row, nrows, col, n)` puts the file range
    `[offset, offset + nrows * n)` into `chunk[row + j, col:col + n]`
    for `j` in `range(nrows)`."""
    remaining = dat_size
    processed = 0
    # Large-block rows while more than one full large row remains
    # (strictly greater, like the reference encodeDatFile loop).  The
    # ten blocks of a row are `large` apart in the file: a read each.
    chunk = min(chunk_size, large)
    if large % chunk != 0:
        raise ValueError(f"chunk {chunk} must divide block size {large}")
    while remaining > large * DATA_SHARDS:
        for b in range(0, large, chunk):
            yield chunk, [(processed + i * large + b, i, 1, 0, chunk)
                          for i in range(DATA_SHARDS)]
        remaining -= large * DATA_SHARDS
        processed += large * DATA_SHARDS
    # Small-block rows, many per coder call: a volume under 10GB is
    # ENTIRELY 1MB small rows, and a (10, 1MB) kernel launch is
    # dominated by its fixed dispatch and transfer cost.  Rows
    # are column-independent, so K consecutive rows stack into one
    # (10, K*small) call — same bytes, K fewer launches; each shard's
    # blocks from consecutive rows are consecutive in its shard file.
    # The ten blocks of a row are consecutive in the file: ONE read
    # scatters them down a column of the chunk.
    rows_per_call = max(1, chunk_size // small)
    row_bytes = small * DATA_SHARDS
    while remaining > 0:
        nrows = min(rows_per_call, -(-remaining // row_bytes))
        yield nrows * small, [
            (processed + r * row_bytes, 0, DATA_SHARDS, r * small, small)
            for r in range(nrows)]
        remaining -= row_bytes * nrows
        processed += row_bytes * nrows


def _pread_into(fd: int, views: list, offset: int) -> None:
    """Fill the 1-D uint8 `views`, in order, from the file at `offset`:
    one `preadv`, another only after a short read.  What lies past the
    end of the file is zeroed: a reused buffer holds an older chunk's
    bytes, and the shards' zero padding is part of the format."""
    while views:
        n = os.preadv(fd, views, offset)
        if n == 0:
            break
        offset += n
        while views and n >= views[0].nbytes:
            n -= views[0].nbytes
            views = views[1:]
        if n:
            views = [views[0][n:]] + views[1:]
    for v in views:
        v[:] = 0


def _read_chunk(fd: int, width: int, reads,
                flat: np.ndarray | None = None, each=map) -> np.ndarray:
    """Build one chunk of `_chunk_spans` in place: no intermediate
    `bytes`, no copy.  In `flat` (a buffer of at least
    `DATA_SHARDS * width` bytes that the caller owns and may reuse) if
    given, else in a fresh array that belongs to whoever gets the
    chunk.  Either way the chunk is C-contiguous — a narrower last
    chunk is viewed out of the front of `flat`, not sliced out of wider
    rows — so `jnp.asarray` does not copy it first.  The reads fill
    disjoint views; `each` (`map`, or an executor's) says whether they
    go out one after the other or side by side."""
    if flat is None:
        flat = np.empty(DATA_SHARDS * width, dtype=np.uint8)
    data = flat[:DATA_SHARDS * width].reshape(DATA_SHARDS, width)

    def read(span) -> None:
        offset, row, nrows, col, n = span
        _pread_into(fd, [data[row + j, col:col + n] for j in range(nrows)],
                    offset)

    for _ in each(read, reads):
        pass
    return data


def _chunk_reader(dat, dat_size: int, large: int, small: int,
                  chunk_size: int):
    """Yield the `(DATA_SHARDS, n)` uint8 stripe chunks of
    `_chunk_spans`, each a fresh array the consumer owns (the batch
    path copies it into a staging buffer of its own)."""
    fd = dat.fileno()
    for width, reads in _chunk_spans(dat_size, large, small, chunk_size):
        yield _read_chunk(fd, width, reads)


class _Countdown:
    """Calls `then()` on the `n`-th `done()`, from whichever thread
    makes it: how a pooled buffer, or a slot of the writers' window,
    goes back when the last of several parties is finished with it."""
    __slots__ = ("_left", "_then", "_lock")

    def __init__(self, n: int, then):
        self._left = n
        self._then = then
        self._lock = threading.Lock()

    def done(self) -> None:
        with self._lock:
            self._left -= 1
            last = self._left == 0
        if last:
            self._then()


class _Writers:
    """The writer stage of `_run_pipeline`: `SEAL_WRITERS` threads that
    call the job's `write(sid, row)` beside the main thread, each row a
    closed `stage` of `clock`.  Shard `sid` is always thread
    `sid % SEAL_WRITERS`'s and a thread writes in the order it was
    handed, so every shard file is written by one thread, in chunk
    order.  A `write` that raises ends the job: the error joins
    `error`, `cancelled` is set, and every thread then lets what it is
    still handed go unwritten (its buffers are released all the same:
    nobody reads them any more)."""

    def __init__(self, write, window: int, clock: StageClock, stage: str,
                 cancelled: threading.Event, error: list):
        self._write, self._clock, self._stage = write, clock, stage
        self._cancelled, self._error = cancelled, error
        self._slots = threading.Semaphore(window)
        self._queues = [queue.SimpleQueue() for _ in range(SEAL_WRITERS)]
        self._threads = [
            threading.Thread(target=self._loop, args=(q,), daemon=True,
                             name=f"ec-write-{i}")
            for i, q in enumerate(self._queues)]
        for t in self._threads:
            t.start()

    def _loop(self, q) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            sid, row, done = item
            if not self._cancelled.is_set():
                try:
                    with self._clock(self._stage, row.nbytes):
                        self._write(sid, row)
                except BaseException as e:  # noqa: BLE001 — surfaced
                    self._error.append(e)   # by _run_pipeline
                    self._cancelled.set()
            if done is not None:
                done()

    def hand(self, first_sid: int, rows, done=None) -> None:
        """Queue `rows[i]` for shard `first_sid + i`; `done()` is
        called once per row, when it is written."""
        for sid, row in enumerate(rows, first_sid):
            self._queues[sid % len(self._queues)].put((sid, row, done))

    def hand_chunk(self, release, first_sid: int, rows) -> None:
        """`hand` for rows that are views of a chunk's pooled buffer:
        `release()` is called when the last of them is written.  Holds
        the caller while the writers are a whole window behind — the
        chunk handed `window` chunks ago is not written yet
        (`SEAL_WRITER` counts how the call found them)."""
        ready = self._slots.acquire(blocking=False)
        SEAL_WRITER.note(ready)
        while not ready:
            if self._cancelled.is_set():
                raise self._error[0]
            ready = self._slots.acquire(timeout=0.2)

        def written() -> None:
            release()
            self._slots.release()

        self.hand(first_sid, rows, _Countdown(len(rows), written).done)

    def join(self) -> None:
        """Wait until everything handed is written (or, after
        `cancelled`, let go), and for the threads to end."""
        for q in self._queues:
            q.put(None)
        for t in self._threads:
            t.join()


def _run_pipeline(chunks, fill, dispatch, flush, *, depth: int,
                  buffers: int, clock: StageClock, wait_stage: str,
                  fill_stage: str, write=None, write_stage: str = "",
                  write_tail_stage: str = "") -> None:
    """The read-ahead pipeline of both EC file jobs (the seal's
    `_pipelined_encode`, `rebuild_ec_files`): the skeleton that owns
    the threads, the pooled buffers and the window of chunks in
    flight; what a chunk IS comes in as three functions, or four.

      reader thread:  wait for a free buffer (the job has `buffers`)
                      data = fill(what, buffer)         `fill_stage`
      main thread:    wait for the next chunk           `wait_stage`
                      handles = dispatch(data)
                      with `depth` chunks in flight:
                        flush(oldest handles, release)
      writer threads, for a job that hands `write`:
                      write(sid, row) of a row the
                        main thread handed them        `write_stage`

    `chunks` yields `(nbytes, what)` per chunk: the bytes of buffer it
    needs and what `fill` is to read into it.  `dispatch` and `flush`
    time their own stages.  `flush` collects the oldest chunk's
    handles, THEN calls `release()`, then writes.  A chunk's buffer
    goes back to `CHUNK_POOL`, and so to the reader, only when its
    results are drained (that `release()`): until then the coder may
    still read it — a device coder transfers asynchronously, and on
    the CPU platform `jnp.asarray` may alias the host array.

    A job that hands `write` writes nothing on the main thread.  Its
    functions are called as `dispatch(data, hand)` and
    `flush(handles, release, hand)` and give rows to the writer threads
    (`_Writers`) with `hand(first_sid, rows)`.  The rows `dispatch`
    hands are views of the chunk's buffer, and it hands them for every
    chunk: the buffer then goes back when they are written AND the
    chunk is drained, whichever comes last, and `dispatch` is held in
    `hand` while the writers are `depth` chunks behind.  The rows
    `flush` hands own their bytes.  When the last chunk is flushed the
    main thread waits for the writers (`write_tail_stage`).

    An error on any thread ends the job: reader and writers are
    cancelled and joined, what never reached the coder goes back to
    the pool, and the first error is raised."""
    q: "queue.Queue" = queue.Queue()
    free = threading.Semaphore(buffers)
    cancelled = threading.Event()
    error: list[BaseException] = []

    def read_loop() -> None:
        try:
            for nbytes, what in chunks:
                # Bounded waits with a cancel check: if the main thread
                # dies (device failure, ENOSPC) it hands no buffer back,
                # and a plain acquire would deadlock the final join
                # forever.
                while not free.acquire(timeout=0.2):
                    if cancelled.is_set():
                        return
                if cancelled.is_set():      # a writer failed
                    return
                buf = CHUNK_POOL.take(nbytes)
                with clock(fill_stage) as st:
                    data = fill(what, buf)
                    st.add_bytes(data.nbytes)
                q.put((data, buf))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            error.append(e)
        finally:
            q.put(None)  # end of stream; the queue has no bound
    t = threading.Thread(target=read_loop, daemon=True,
                         name="ec-read-ahead")
    t.start()
    writers = None if write is None else \
        _Writers(write, depth, clock, write_stage, cancelled, error)
    # per chunk in flight: its handles, and the `release` its drain
    # is to call
    inflight: "collections.deque" = collections.deque()

    def give_back(buf) -> None:
        CHUNK_POOL.give(buf)
        free.release()

    def drain_oldest() -> None:
        handles, release = inflight.popleft()
        if writers is None:
            flush(handles, release)
        else:
            flush(handles, release, writers.hand)

    try:
        while True:
            with clock(wait_stage):
                item = q.get()
            if item is None:
                break
            data, buf = item
            if cancelled.is_set():          # a writer failed
                CHUNK_POOL.give(buf)
                break
            release = functools.partial(give_back, buf)
            if writers is None:
                handles = dispatch(data)
            else:
                # two may still read the buffer: the coder until the
                # drain, the writers until its data rows are written
                release = _Countdown(2, release).done
                handles = dispatch(
                    data, functools.partial(writers.hand_chunk, release))
            inflight.append((handles, release))
            if len(inflight) >= depth:
                drain_oldest()
        while inflight and not cancelled.is_set():
            drain_oldest()
        if writers is not None:
            with clock(write_tail_stage):
                writers.join()
    finally:
        cancelled.set()
        t.join()
        if writers is not None:
            writers.join()
        # After a failure: what never reached the coder goes back to
        # the pool; a chunk that was in flight is dropped with its
        # buffer, which the coder may still read.
        while not q.empty():
            item = q.get_nowait()
            if item is not None:
                CHUNK_POOL.give(item[1])
    if error:
        raise error[0]


def _pipelined_encode(fd: int, spans, coder: ErasureCoder, outputs,
                      accs=None, clock: StageClock | None = None):
    """The seal's encode pipeline (SURVEY §2.3 'double-buffered
    host→HBM DMA + batched kernel launches') over the chunks `spans`
    (`_chunk_spans`) of the file `fd`, on `_run_pipeline`, each step a
    stage of `clock` (stats/roofline.py STAGES).  The main thread
    writes nothing:

      reader thread:  wait for a free buffer
                      read chunk k+1 into it,
                        SEAL_READERS reads at once   seal.stack
      main thread:    wait for chunk k               seal.stack_wait
                      issue H2D of k, launch,
                        request D2H                  seal.dispatch
                      hand the data rows of k to
                        the writers                  seal.write_data
                      collect parity of
                        k-(SEAL_DEPTH-1)             seal.drain
                      hand it to the writers         seal.write_parity
      writer threads: write a row into its shard
                        file, SEAL_WRITERS at once   beside.seal_write

    This is a caller of the coder that drains later, so it asks for the
    unfenced call (`encode_unfenced`, where the coder has one;
    `RooflineLedger.record` takes fenced walls only, so that call
    records no kernel row) and for the copy back (where the handle can
    copy asynchronously).  With the writes beside it the main thread's
    turn is shorter than the device round trip of a chunk —
    host→device, kernel, device→host — so the parity is collected
    `SEAL_DEPTH - 1` chunks later, when its bytes are on the host
    (`SEAL_INFLIGHT` counts how often).  A host coder computes inside
    dispatch and its arrays count as ready.  A device error surfaces at
    the drain.

    The rows go to the writers as views — of the pooled chunk, of the
    collected parity — and a shard file is one writer thread's, in
    chunk order, so `_shard_write` sees each shard's bytes in file
    order (the byte accumulators of the non-fused path depend on it).
    `seal.write_data` is the hand-over and, if the writers are a whole
    window behind, the wait for them (`SEAL_WRITER` counts how often);
    `seal.write_parity` the hand-over and, once, the wait for the last
    rows.  When this function returns every row is in its file.

    The chunks live in `SEAL_BUFFERS` buffers of `CHUNK_POOL`
    (`SEAL_DEPTH` in flight or being written, one read ahead, one
    being filled), and that count is what bounds the read-ahead.  A
    buffer goes back to the pool only when its chunk is finished —
    its data rows written AND its parity drained.

    When ``accs is None`` the coder must support fused CRC
    (`encode_with_crc`) and every chunk must span whole `.ecc` blocks:
    the kernel emits every shard's per-block CRC32-C as a second output
    and this function returns the per-shard CRC lists.  With byte
    accumulators passed, None is returned."""
    if clock is None:
        clock = StageClock()
    data_shards = coder.data_shards
    fused = accs is None
    unfenced = getattr(coder, "encode_unfenced", None)
    crc_lists: list[list[int]] = \
        [[] for _ in range(data_shards + coder.parity_shards)]
    readers = ThreadPoolExecutor(SEAL_READERS,
                                 thread_name_prefix="ec-seal-read")

    def fill(span, buf):
        return _read_chunk(fd, *span, buf, readers.map)

    def write(sid, row) -> None:
        _shard_write(outputs[sid], sid, row, accs)

    def dispatch(data, hand):
        # Dispatch first: a device coder's transfer, kernel and copy
        # back run beside the next chunks' turns.
        with clock("seal.dispatch", data.nbytes):
            if unfenced is not None:
                handles = unfenced(data, crc=fused)
            elif fused:
                handles = coder.encode_with_crc(data)
            else:
                handles = (coder.encode(data),)
            for h in handles:
                _request_copy_back(h)
        with clock("seal.write_data", data.nbytes):
            hand(0, data)
        return handles

    def flush(handles, release, hand) -> None:
        with clock("seal.drain") as st:
            SEAL_INFLIGHT.note(all(_is_ready(h) for h in handles))
            parity = np.asarray(handles[0])
            st.add_bytes(parity.nbytes)
            if fused:
                crcs = np.asarray(handles[1])
                for sid, row in enumerate(crcs):
                    crc_lists[sid].extend(int(c) for c in row)
                st.add_bytes(crcs.nbytes)
        # The coder is done with the oldest chunk: its buffer goes back
        # as soon as the writers are done with its data rows too.
        release()
        with clock("seal.write_parity", parity.nbytes):
            hand(data_shards, parity)

    try:
        _run_pipeline(((DATA_SHARDS * width, (width, reads))
                       for width, reads in spans),
                      fill, dispatch, flush, depth=SEAL_DEPTH,
                      buffers=SEAL_BUFFERS, clock=clock,
                      wait_stage="seal.stack_wait", fill_stage="seal.stack",
                      write=write, write_stage="beside.seal_write",
                      write_tail_stage="seal.write_parity")
    finally:
        readers.shutdown()
    return dict(enumerate(crc_lists)) if fused else None


def rebuild_ec_files(base_file_name: str,
                     coder: ErasureCoder | None = None,
                     chunk_size: int = DEFAULT_CHUNK,
                     clock: StageClock | None = None) -> list[int]:
    """Recreate missing .ec?? files from survivors (RebuildEcFiles).

    Returns the list of generated shard ids.  Layout-agnostic: operates
    on flat shard-file columns.  Codec-aware: the codec comes from the
    `.vif` sidecar, the shard count from the codec, and only the
    codec's planned minimal read set is read from disk — an LRC
    in-group rebuild reads 5 shard files, not every survivor.

    The chunks run on `_run_pipeline`, as the seal's do, each step a
    stage of `clock` (the job's stage clock, as in `write_ec_files`):

      reader thread:  wait for a free buffer
                      preadv chunk k+1 of every   beside.rebuild_read
                        planned survivor into it,
                        REBUILD_READERS at once:
                        one (survivors, n) array
      main thread:    wait for chunk k            rebuild.read
                      ONE H2D of k, launch,
                        request D2H               rebuild.dispatch
                      collect the rebuilt rows
                        of k-(REBUILD_DEPTH-1)    rebuild.drain
                      hand its buffer back
                      CRC + write them            rebuild.write

    The coder is called unfenced where it can be
    (`reconstruct_unfenced`: no kernel row, see `_pipelined_encode`)
    and the rows are collected `REBUILD_DEPTH - 1` chunks later, by
    when their round trip — two to three of this loop's turns — is
    over (`REBUILD_INFLIGHT` counts how often).  A host coder
    reconstructs inside dispatch.  The chunks live in `REBUILD_BUFFERS`
    buffers of `CHUNK_POOL`; a narrower read set (LRC in-group: 5
    survivors) takes the front of one."""
    if coder is None:
        coder = new_coder(codec=ec_codec_name(base_file_name))
    cd = getattr(coder, "codec", None) or get_codec("rs")
    if clock is None:
        clock = StageClock(cd.name)
    present: dict[int, str] = {}
    missing: list[int] = []
    for sid in range(cd.total_shards):
        path = base_file_name + to_ext(sid)
        if os.path.exists(path):
            present[sid] = path
        else:
            missing.append(sid)
    if not missing:
        return []
    try:
        plan = cd.repair_plan(tuple(present), missing)
    except ValueError as e:
        raise ValueError(
            f"too few shards to rebuild: {len(present)} survive "
            f"({cd.name}): {e}") from None
    needed = sorted({sid for p in plan for sid in p.reads})

    shard_size = os.path.getsize(next(iter(present.values())))
    for sid, path in present.items():
        if os.path.getsize(path) != shard_size:
            raise ValueError(f"ec shard size mismatch on {path}")

    ins = {sid: open(present[sid], "rb") for sid in needed}
    outs = {sid: open(base_file_name + to_ext(sid), "wb") for sid in missing}
    accs = {sid: BlockCrcAccumulator() for sid in missing}
    unfenced = getattr(coder, "reconstruct_unfenced", None)

    readers = ThreadPoolExecutor(REBUILD_READERS,
                                 thread_name_prefix="ec-rebuild-read")

    def fill(span, buf):
        off, take = span
        data = buf[:len(needed) * take].reshape(len(needed), take)

        def read_row(row, sid) -> None:
            if os.preadv(ins[sid].fileno(), [row], off) != take:
                raise ValueError(f"short read on shard {sid}")

        for _ in readers.map(read_row, data, needed):
            pass
        ec_repair_read_bytes_total.inc(data.nbytes, codec=cd.name)
        return data

    def dispatch(data):
        with clock("rebuild.dispatch", data.nbytes):
            if unfenced is not None:
                handles = (unfenced(needed, data, missing),)
            else:
                rec = coder.reconstruct(dict(zip(needed, data)),
                                        wanted=missing)
                handles = tuple(rec[sid] for sid in missing)
            for h in handles:
                _request_copy_back(h)
        return handles

    def flush(handles, release) -> None:
        with clock("rebuild.drain") as st:
            REBUILD_INFLIGHT.note(all(_is_ready(h) for h in handles))
            # one (rebuilt, n) array, or a host coder's row per shard
            rows = [row for h in handles
                    for row in np.atleast_2d(np.asarray(h))]
            nbytes = sum(row.nbytes for row in rows)
            st.add_bytes(nbytes)
        release()
        with clock("rebuild.write", nbytes):
            for sid, row in zip(missing, rows):
                _shard_write(outs[sid], sid, row, accs)

    def spans():
        for off in range(0, shard_size, chunk_size):
            take = min(chunk_size, shard_size - off)
            yield len(needed) * take, (off, take)

    try:
        _run_pipeline(spans(), fill, dispatch, flush, depth=REBUILD_DEPTH,
                      buffers=REBUILD_BUFFERS, clock=clock,
                      wait_stage="rebuild.read",
                      fill_stage="beside.rebuild_read")
    finally:
        readers.shutdown()
        with clock("rebuild.finish"):
            for f in ins.values():
                f.close()
            for f in outs.values():
                f.close()
    with clock("rebuild.finish"):
        # Load-modify-save of the shared sidecar: serialize with the
        # other writers (shard receive, scrub TOFU) or concurrent
        # savers lose each other's entries.
        with ecc_lock(base_file_name):
            ecc = ShardChecksums.load(base_file_name)
            for sid in missing:
                ecc.set_shard(sid, accs[sid].finalize())
            ecc.save()
    return missing
