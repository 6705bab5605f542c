"""Cluster-integrated batched EC encode: many volumes, one mesh step.

The encode mirror of `cluster_rebuild`: pull quiet/full volumes'
`.dat`/`.idx` from their servers, stack stripe chunks from MANY volumes
on the mesh's "vol" axis, compute all parity in batched GF(2)
bit-matmuls (`sharded_codec.batched_encode_with_crc` — shard_map over
("vol", "col"), zero collectives), then scatter the shards + `.ecx`
across the cluster, mount them, and delete the original replicas.

The data path is STREAMED, not lockstep (ROADMAP 1): a prefetch thread
stacks the next chunk batch into a reusable host buffer while the
device computes the current one and a drain thread fences completed
parity and appends shard files — per-chunk wall time approaches
max(stage) instead of sum(stages) (stream_pipeline.py; the overlap is
visible in the `batch_*` stage histograms, whose per-stage sums exceed
the wall clock).  The encode kernel also emits every shard's per-block
CRC32-C on device (ops/crc_fold.py), so the `.ecc` sidecar ships to
each holder ready-made and `receive_shard` skips its CPU re-read of
the pushed bytes.

The reference encodes one volume at a time ON its own server
(weed/shell/command_ec_encode.go:92-264 →
VolumeEcShardsGenerate, server/volume_grpc_erasure_coding.go:40); this
is the SURVEY §2.3 "shard scatter after encode" mapping instead —
encoding N quiet volumes is embarrassingly data-parallel over chips,
and the per-volume chunking reuses the exact `_chunk_reader` the local
encoder uses, so shard bytes stay byte-identical to `ec.encode`
(the golden-gate layout).

Shell entry point: `ec.encode -batch` (shell/command_ec.py).
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import tempfile
import threading
import time

import numpy as np

from ..cluster import rpc
from ..events import emit as emit_event
from ..fault import registry as _fault
from ..stats import flows as _flows
from ..stats.metrics import observe_batch_stage, stage_attrs
from ..trace import root_span
from ..codecs import get_codec
from ..ec import DATA_SHARDS, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, to_ext
from ..ec.encoder import (DEFAULT_CHUNK, _chunk_reader,
                          write_sorted_file_from_idx)
from ..ec.volume_info import update_volume_info
from ..ops import crc_fold
from ..stats import roofline as _roofline
from .cluster_rebuild import _pad_to, make_mesh
from .sharded_codec import (batched_encode, batched_encode_with_crc,
                            record_fenced_batch)
from .stream_pipeline import PipelineRecorder, run_pipeline

# Column padding granularity — matches cluster_rebuild: keeps the
# jitted matmul's N lane-aligned and divisible by any col axis <= 16,
# and collapses ragged tail-chunk widths onto few compiled shapes.
_COL_ALIGN = 2048


# Chunks in flight between prefetch and drain (stream_pipeline.py).
PIPELINE_DEPTH = 2

fused_crc_enabled = crc_fold.fused_crc_enabled

# Cap on concurrent in-flight shard payload bytes during scatter — a
# 30GB volume batch must not hold ~14 whole shard files in memory at
# once (shards are read inside the budgeted workers, not up front).
SCATTER_BUDGET_BYTES = 256 << 20


class _ByteBudget:
    """Blocking byte-count semaphore; a request larger than the cap is
    clamped so a single huge shard can always proceed alone."""

    def __init__(self, cap: int):
        self.cap = max(1, cap)
        self._used = 0
        self._cond = threading.Condition()

    def acquire(self, nbytes: int) -> int:
        take = min(nbytes, self.cap)
        with self._cond:
            while self._used + take > self.cap:
                self._cond.wait()
            self._used += take
        return take

    def release(self, taken: int) -> None:
        with self._cond:
            self._used -= taken
            self._cond.notify_all()


class _BufferPool:
    """Reusable host staging buffers for the stacked chunk batches.

    The pipeline recycles a buffer only after its chunk has been fenced
    and written (drain), so at most `slots` stacked batches exist — the
    bounded-memory half of the double-buffering story."""

    def __init__(self, slots: int, shape: tuple[int, int, int],
                 cancel: threading.Event | None = None):
        self._free: list[np.ndarray] = []
        self._slots = slots
        self._shape = shape
        self._cond = threading.Condition()
        self._made = 0
        # Shared with the stream pipeline: if the drain stage dies, no
        # release() is ever coming — a producer blocked here must
        # observe the cancellation instead of deadlocking the
        # pipeline's final join.
        self._cancel = cancel

    def acquire(self) -> np.ndarray:
        with self._cond:
            while not self._free and self._made >= self._slots:
                if self._cancel is not None and self._cancel.is_set():
                    raise RuntimeError("encode pipeline cancelled")
                self._cond.wait(0.2)
            if self._free:
                # Recycled buffers keep their stale bytes: the producer
                # zeroes exactly the padding regions of the view it
                # stacks into (row tails past each chunk's width, rows
                # past the live volume count) — a full fill(0) here
                # would cost an extra whole-buffer memory pass per
                # chunk batch on the host hot path.
                buf = self._free.pop()
            else:
                self._made += 1
                buf = np.zeros(self._shape, np.uint8)
        return buf

    def release(self, buf: np.ndarray) -> None:
        with self._cond:
            self._free.append(buf)
            self._cond.notify()


def batch_encode(env, vids, mesh=None, max_batch_bytes=1 << 28,
                 workers: int = 8, chunk_size: int = DEFAULT_CHUNK,
                 progress=None, codec=None) -> list[str]:
    """EC-encode `vids` across the cluster in mesh-batched steps.
    Returns one human-readable line per volume.  `codec` selects the
    erasure codec ("rs" default / "lrc"): the generator matrix, shard
    count, and the .vif codec id pushed to every holder derive from it.

    env: duck-typed cluster view (shell CommandEnv): volume_locations,
    data_nodes, vs_call.
    """
    if not SMALL_BLOCK_SIZE <= chunk_size <= LARGE_BLOCK_SIZE:
        # The staging-buffer capacity is sized to min(chunk_size,
        # LARGE_BLOCK_SIZE), but the small-row reader yields widths up
        # to chunk_size — a larger value would broadcast-fail
        # mid-encode AFTER replicas were frozen.  Refuse up front.
        raise ValueError(
            f"chunk_size {chunk_size} must be within "
            f"[{SMALL_BLOCK_SIZE}, {LARGE_BLOCK_SIZE}]")
    if LARGE_BLOCK_SIZE % chunk_size != 0:
        # _chunk_reader enforces this mid-stream on the first
        # large-block row — same refuse-before-freeze rationale.
        raise ValueError(
            f"chunk_size {chunk_size} must divide the large block "
            f"size {LARGE_BLOCK_SIZE}")
    codec = get_codec(codec)
    if mesh is None:
        mesh = make_mesh()
    # One size map per batch call — not an O(volumes x nodes) rescan
    # of the full topology per volume.
    sizes: dict[int, int] = {}
    for n in env.data_nodes():
        for v in n["volumes"]:
            sizes.setdefault(v["id"], int(v["size"]))
    targets: list[tuple[int, list[str]]] = []
    messages: list[str] = []
    for vid in vids:
        try:
            locs = env.volume_locations(vid)
        except rpc.RpcError as e:
            if e.status != 404:
                raise
            locs = []
        if not locs:
            messages.append(f"volume {vid}: SKIPPED — no locations")
            continue
        targets.append((vid, locs))
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=workers)
    try:
        i = 0
        while i < len(targets):
            batch, total = [], 0
            while i < len(targets) and (not batch
                                        or total < max_batch_bytes):
                batch.append(targets[i])
                total += sizes.get(targets[i][0], 0)
                i += 1
            messages += _encode_batch_group(env, mesh, pool, batch,
                                            chunk_size, progress, codec)
    finally:
        # cancel_futures: queued fetch/scatter work from a failed batch
        # must not keep running (and keep connections pinned) after the
        # exception has already unwound to the caller.
        pool.shutdown(wait=False, cancel_futures=True)
    return messages


def _fetch_volume(tmpdir: str, vid: int, locs: list[str]) -> str:
    """Freeze + pull one volume's .dat/.idx to local temp files,
    failing over across replicas.  Returns the local base path."""
    base = os.path.join(tmpdir, str(vid))
    errors = []
    for url in locs:
        try:
            if _fault.ARMED:
                _fault.hit("ec.fetch_shard", holder=url, vid=vid)
            _h = {**rpc.PRIORITY_LOW, **_flows.tag("ec.gather")}
            rpc.call_to_file(
                f"http://{url}/admin/volume_file?volume={vid}&ext=.idx",
                base + ".idx", headers=_h)
            rpc.call_to_file(
                f"http://{url}/admin/volume_file?volume={vid}&ext=.dat",
                base + ".dat", headers=_h)
            return base
        except Exception as e:  # noqa: BLE001 — next replica
            errors.append(f"{url}: {type(e).__name__}: {e}")
    raise rpc.RpcError(
        502, f"volume {vid}: cannot fetch .dat/.idx: "
             + "; ".join(errors[:4]))


def _encode_batch_group(env, mesh, pool, batch, chunk_size,
                        progress, codec) -> list[str]:
    """Fetch, mesh-encode, scatter one sub-batch of volumes — journaled
    as ec.encode.start/finish with per-stage byte/second attrs, under a
    root span so the timeline row links to a /debug/traces trace."""
    vids = [vid for vid, _locs in batch]
    with root_span("ec.batch_encode", "ec", volumes=len(vids),
                   codec=codec.name):
        emit_event("ec.encode.start", volumes=vids, batch=True,
                   codec=codec.name)
        t0 = time.perf_counter()
        stages: dict[str, list[float]] = {}  # stage -> [seconds, bytes]
        try:
            out = _encode_batch_group_inner(env, mesh, pool, batch,
                                            chunk_size, progress,
                                            stages, codec)
        except Exception as e:
            emit_event("ec.encode.finish", severity="error",
                       volumes=vids, batch=True, codec=codec.name,
                       seconds=round(time.perf_counter() - t0, 6),
                       error=f"{type(e).__name__}: {e}",
                       **stage_attrs(stages))
            raise
        emit_event("ec.encode.finish", volumes=vids, batch=True,
                   codec=codec.name, pipeline_depth=PIPELINE_DEPTH,
                   seconds=round(time.perf_counter() - t0, 6),
                   **stage_attrs(stages))
        return out


def _encode_batch_group_inner(env, mesh, pool, batch, chunk_size,
                              progress, stages, codec) -> list[str]:
    """Fetch, stream-encode, scatter one sub-batch of volumes."""
    from ..shell.command_ec import balanced_distribution, collect_ec_nodes
    vol_axis = mesh.shape["vol"]
    col_axis = mesh.shape["col"]
    # Fused device CRCs need every stacked width to cover whole `.ecc`
    # blocks per mesh column; `_chunk_reader` widths are always 1MB
    # multiples when chunk_size is, so the only cost is column padding
    # up to BLOCK x col instead of 2048 x col.
    fused = fused_crc_enabled() and chunk_size % SMALL_BLOCK_SIZE == 0
    align = SMALL_BLOCK_SIZE * col_axis if fused \
        else _pad_to(_COL_ALIGN, col_axis * 8)
    out: list[str] = []
    with tempfile.TemporaryDirectory(prefix="ec_batch_encode_") as tmp:
        # 1. Freeze every replica, then pull .dat/.idx in parallel.
        for vid, locs in batch:
            for url in locs:
                env.vs_call(url, "/admin/readonly",
                            {"volume": vid, "readonly": True})
        t_fetch = time.perf_counter()
        bases = list(pool.map(
            lambda t: _fetch_volume(tmp, *t), batch))
        observe_batch_stage(
            stages, "batch_fetch", time.perf_counter() - t_fetch,
            sum(os.path.getsize(b + ".dat") for b in bases))

        # 2. Stream-encode: stripe chunks stacked on "vol", prefetch /
        # device / drain overlapped (module docstring).  Each volume's
        # chunk sequence is the exact local-encoder chunking
        # (byte-identical shards); columns are zero-padded (parity is
        # columnwise for every codec, so padded columns are discarded
        # zeros, never corruption).
        writers = [_ShardWriter(b, codec.total_shards) for b in bases]
        # Per-volume, per-shard `.ecc` block CRCs from the device.
        vol_crcs: list[list[list[int]]] = \
            [[[] for _ in range(codec.total_shards)] for _ in bases]
        dats = [open(b + ".dat", "rb") for b in bases]
        n_cap = _pad_to(max(SMALL_BLOCK_SIZE,
                            min(chunk_size, LARGE_BLOCK_SIZE)), align)
        v_cap = _pad_to(len(bases), vol_axis)
        cancel = threading.Event()
        buffers = _BufferPool(PIPELINE_DEPTH + 1,
                              (v_cap, DATA_SHARDS, n_cap),
                              cancel=cancel)
        # Always-on (bounded) production recorder: per-batch stage
        # spans feed the roofline plane's occupancy/gantt surfaces.
        rec = PipelineRecorder(maxlen=1024) if _roofline.ARMED else None
        try:
            iters = [
                _chunk_reader(d, os.path.getsize(b + ".dat"),
                              LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE,
                              chunk_size)
                for d, b in zip(dats, bases)]

            def produce():
                active = list(range(len(iters)))
                bi = 0
                while active:
                    t_stack = time.perf_counter()
                    chunks, produced = [], []
                    for v in active:
                        try:
                            chunks.append(next(iters[v]))
                            produced.append(v)
                        except StopIteration:
                            pass
                    if not chunks:
                        break
                    widths = [c.shape[1] for c in chunks]
                    n_pad = _pad_to(max(widths), align)
                    v_pad = _pad_to(len(chunks), vol_axis)
                    # Backpressure wait (drain hasn't recycled a buffer
                    # yet) is pipeline idle time, not stacking work —
                    # keep it out of the batch_stack histogram or a
                    # device-bound run reads as stack-bound.
                    t_wait0 = time.perf_counter()
                    buf = buffers.acquire()
                    t_wait1 = time.perf_counter()
                    t_wait = t_wait1 - t_wait0
                    stacked = buf[:v_pad, :, :n_pad]
                    for j, c in enumerate(chunks):
                        stacked[j, :, :c.shape[1]] = c
                        stacked[j, :, c.shape[1]:] = 0
                    stacked[len(chunks):] = 0
                    t_end = time.perf_counter()
                    observe_batch_stage(
                        stages, "batch_stack",
                        t_end - t_stack - t_wait,
                        sum(widths) * DATA_SHARDS)
                    if rec is not None:
                        # Two segments: the buffer-pool wait between
                        # them is idle backpressure, not stack work.
                        rec.note_span("stack", bi, t_stack, t_wait0)
                        rec.note_span("stack", bi, t_wait1, t_end)
                    yield (buf, stacked, list(produced), widths, bi)
                    bi += 1
                    active = produced

            def dispatch(item):
                buf, stacked, active, widths, bi = item
                t_d0 = time.perf_counter()
                if fused:
                    parity, crcs = batched_encode_with_crc(
                        stacked, mesh, codec=codec.name)
                else:
                    parity = batched_encode(stacked, mesh,
                                            codec=codec.name)
                    crcs = None
                t_d1 = time.perf_counter()
                if rec is not None:
                    rec.note_span("dispatch", bi, t_d0, t_d1)
                return (buf, parity, crcs, active, widths,
                        stacked.nbytes, bi, t_d0, t_d1)

            def drain(handle):
                (buf, parity, crcs, active, widths, nbytes, bi,
                 t_d0, t_d1) = handle
                # np.asarray fences the dispatch (device->host copy):
                # this stage is the EXPOSED device+transfer wait — with
                # the pipeline overlapping, its per-batch sum exceeds
                # the wall-clock share it actually costs.
                t_dev = time.perf_counter()
                parity = np.asarray(parity)
                if crcs is not None:
                    crcs = np.asarray(crcs)
                t_fence = time.perf_counter()
                observe_batch_stage(stages, "batch_encode_device",
                                    t_fence - t_dev, nbytes)
                if rec is not None:
                    # Device busy is observable only as [dispatch end,
                    # drain fence]: includes q_out queueing, so it is
                    # an upper bound on true kernel occupancy.
                    rec.note_span("device", bi, t_d1, t_fence)
                if _roofline.ARMED:
                    record_fenced_batch(
                        "batch_encode", codec.name,
                        out_rows=int(parity.shape[1]),
                        in_rows=DATA_SHARDS, n=int(parity.shape[2]),
                        batch=int(parity.shape[0]),
                        crc=crcs is not None,
                        seconds=t_fence - t_d0,
                        measured_bytes=int(nbytes) + parity.nbytes)
                t_wr = time.perf_counter()
                written = 0
                for j, v in enumerate(active):
                    w = widths[j]
                    writers[v].write(buf[j, :, :w], parity[j, :, :w])
                    written += w * (DATA_SHARDS + parity.shape[1])
                    if crcs is not None:
                        nb = w // SMALL_BLOCK_SIZE
                        for sid in range(codec.total_shards):
                            vol_crcs[v][sid].extend(
                                int(c) for c in crcs[j, sid, :nb])
                t_wr1 = time.perf_counter()
                observe_batch_stage(stages, "batch_write",
                                    t_wr1 - t_wr, written)
                if rec is not None:
                    rec.note_span("drain", bi, t_wr, t_wr1)
                buffers.release(buf)

            run_pipeline(produce(), dispatch, drain, depth=PIPELINE_DEPTH,
                         cancel=cancel, recorder=rec)
            for w in writers:
                w.finish()
            if rec is not None:
                _roofline.LEDGER.note_pipeline("encode", rec)
        finally:
            for d in dats:
                d.close()

        # 3. .ecx from the fetched .idx (WriteSortedFileFromIdx), and
        # a .vif carrying the needle version + codec id — every shard
        # holder must know which generator matrix made its shards.
        for base in bases:
            write_sorted_file_from_idx(base)
            with open(base + ".dat", "rb") as f:
                version = f.read(1)[0]
            update_volume_info(base, version=version, codec=codec.name)

        # 4. Scatter: balanced placement; push the device-computed
        # `.ecc` fragment FIRST (so receive_shard skips its CPU CRC
        # pass over the pushed bytes), then shards under the byte
        # budget, then .ecx/.vif, mount, delete the originals
        # (command_ec_encode.go flow).
        budget = _ByteBudget(SCATTER_BUDGET_BYTES)
        for b_idx, ((vid, locs), base) in enumerate(zip(batch, bases)):
            plan = balanced_distribution(collect_ec_nodes(env),
                                         n_shards=codec.total_shards)
            t_scatter = time.perf_counter()
            pusher = _ecc_push_plan(
                vid, ((url, sid, vol_crcs[b_idx][sid])
                      for url, sids in plan.items()
                      for sid in sids)) if fused else None
            futs = []
            for url, shard_ids in plan.items():
                for sid in shard_ids:
                    futs.append(pool.submit(
                        _scatter_shard, url, vid, sid,
                        base + to_ext(sid), budget, pusher))
            scattered = sum(f.result() for f in futs)
            observe_batch_stage(stages, "batch_scatter",
                           time.perf_counter() - t_scatter, scattered)
            with open(base + ".ecx", "rb") as f:
                ecx = f.read()
            with open(base + ".vif", "rb") as f:
                vif = f.read()
            for url in plan:
                _h = {**rpc.PRIORITY_LOW,
                      **_flows.tag("ec.scatter")}
                rpc.call(f"http://{url}/admin/ec/receive_file?"
                         f"volume={vid}&ext=.ecx", "POST", ecx, 600.0,
                         headers=_h)
                rpc.call(f"http://{url}/admin/ec/receive_file?"
                         f"volume={vid}&ext=.vif", "POST", vif, 600.0,
                         headers=_h)
                env.vs_call(url, "/admin/ec/mount", {"volume": vid})
            for url in locs:
                env.vs_call(url, "/admin/delete_volume", {"volume": vid})
            line = (f"volume {vid} -> ec shards on {len(plan)} "
                    "servers: "
                    + ", ".join(f"{u}:{s}"
                                for u, s in sorted(plan.items())))
            out.append(line)
            if progress:
                progress(line)
    return out


class _EccOncePush:
    """Once-per-holder push of the kernel-computed `.ecc` fragment,
    run lazily inside the scatter workers: the first shard worker bound
    for a holder ships that holder's fragment under its lock — so the
    entries land BEFORE any shard body and receive_shard can skip its
    CPU pass — while workers for other holders proceed in parallel.  A
    slow/unresponsive holder stalls only its own shard pushes, never
    the drain thread or the whole scatter loop (the fragments are
    best-effort: a holder that missed its fragment just fingerprints
    the pushed bodies as before)."""

    def __init__(self, vid: int, docs: dict[str, dict]):
        self._vid = vid
        self._docs = docs
        self._locks = {u: threading.Lock() for u in docs}

    def ensure(self, url: str) -> None:
        lock = self._locks.get(url)
        if lock is None:
            return
        with lock:
            doc = self._docs.pop(url, None)
            if doc is None:
                return  # already shipped (or the attempt failed)
            try:
                rpc.call(
                    f"http://{url}/admin/ec/receive_ecc?"
                    f"volume={self._vid}", "POST",
                    json.dumps(doc).encode(), 60.0,
                    headers={**rpc.PRIORITY_LOW,
                             **_flows.tag("ec.scatter")})
            except (rpc.RpcError, OSError):
                # Best effort: holder recomputes from the body.  OSError
                # covers connection-level failures (ConnectError,
                # resets, socket timeouts) that are NOT RpcError — a
                # flaky holder must not abort the whole scatter over an
                # optimization.
                pass


def _ecc_push_plan(vid: int, entries) -> _EccOncePush:
    """Build the per-holder `.ecc` fragments from `(holder_url, sid,
    crcs)` triples — the ONE place the fragment wire format (block key,
    8-hex-digit CRCs) is written, shared by encode scatter and rebuild
    scatter.  The CRCs come from the encode kernel, i.e. the intended
    bytes, so wire or disk divergence after this point is detectable by
    the first scrub."""
    docs: dict[str, dict] = {}
    for url, sid, crcs in entries:
        doc = docs.setdefault(
            url, {"block": SMALL_BLOCK_SIZE, "shards": {}})
        doc["shards"][str(sid)] = [f"{c:08x}" for c in crcs]
    return _EccOncePush(vid, docs)


def _scatter_shard(url: str, vid: int, sid: int, path: str,
                   budget: _ByteBudget,
                   ecc_push: _EccOncePush | None = None) -> int:
    """Push one encoded shard to its placement target.  The file is
    read HERE, inside the budgeted worker — the submit loop never holds
    payload bytes, and `budget` caps total in-flight bytes."""
    # Fragment first, BEFORE taking budget or reading the file: workers
    # queued on a slow holder's _EccOncePush lock must idle empty-handed
    # — holding budget bytes there would starve pushes to healthy
    # holders of the 256MB cap.
    if ecc_push is not None:
        ecc_push.ensure(url)
    size = os.path.getsize(path)
    taken = budget.acquire(size)
    try:
        with open(path, "rb") as f:
            payload = f.read()
        if _fault.ARMED:
            _fault.hit("ec.scatter", target=url, vid=vid, shard=sid)
        rpc.call(f"http://{url}/admin/ec/receive_shard?"
                 f"volume={vid}&shard={sid}", "POST", payload, 600.0,
                 headers={**rpc.PRIORITY_LOW,
                          **_flows.tag("ec.scatter")})
        return size
    finally:
        budget.release(taken)


class _ShardWriter:
    """Appends stripe chunks to the codec's local shard files of one
    volume in arrival order — the same order `write_ec_files` writes
    them."""

    def __init__(self, base: str, total_shards: int):
        self.files = [open(base + to_ext(i), "wb")
                      for i in range(total_shards)]

    def write(self, data: np.ndarray, parity: np.ndarray) -> None:
        for i in range(DATA_SHARDS):
            self.files[i].write(data[i].tobytes())
        for p in range(parity.shape[0]):
            self.files[DATA_SHARDS + p].write(parity[p].tobytes())

    def finish(self) -> None:
        for f in self.files:
            f.close()
