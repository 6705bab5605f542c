"""Cluster-integrated batched EC rebuild: many volumes, one mesh step.

This is the production bridge between the cluster RPC world and the
mesh codec (`sharded_codec.batched_reconstruct`): gather survivor
shards from their volume-server holders over HTTP, stack volumes on
the `vol` mesh axis, rebuild EVERY missing shard of EVERY volume in
one jitted GF(2) bit-matmul per survivor-signature group, then scatter
the rebuilt shards back onto cluster nodes and mount them.

The reference rebuilds one volume at a time on one node
(weed/shell/command_ec_rebuild.go:57 — copy survivors to a rebuilder,
local Go RS decode, weed/storage/store_ec.go:322-376); here the decode
is batched over a `jax.sharding.Mesh` so a 256-volume rebuild is a
handful of compiled steps with volumes data-parallel over chips and
byte columns sharded over the `col` axis (BASELINE configs #3/#5).

Shell entry point: `ec.rebuild -batch` (shell/command_ec.py).
"""

from __future__ import annotations

import concurrent.futures
import time
from dataclasses import dataclass, field

import numpy as np

from ..cluster import rpc
from ..codecs import Codec, get_codec
from ..ec import SMALL_BLOCK_SIZE
from ..ec.shard_bits import ShardBits
from ..events import emit as emit_event
from ..fault import registry as _fault
from ..stats import flows as _flows
from ..stats import roofline as _roofline
from ..stats.metrics import (ec_repair_read_bytes_total,
                             observe_batch_stage, stage_attrs)
from ..trace import root_span
from ..utils import env_float as _env_float
from .sharded_codec import (batched_reconstruct,
                            batched_reconstruct_with_crc,
                            record_fenced_batch)
from .stream_pipeline import PipelineRecorder, run_pipeline

# Column padding granularity: keeps the jitted matmul's N divisible by
# the mesh col axis and lane-aligned (128 lanes) for any mesh <= 16 wide.
_COL_ALIGN = 2048


# Shard-fetch budgets: each holder attempt gets a bounded slice of a
# total per-shard deadline, so one dead holder costs one attempt
# timeout — never a 600s hang that stalls the whole batch (the old
# behavior: a single all-purpose 600s timeout per call).
FETCH_ATTEMPT_TIMEOUT = _env_float(
    "SEAWEEDFS_TPU_EC_FETCH_TIMEOUT", 30.0)
FETCH_TOTAL_DEADLINE = _env_float(
    "SEAWEEDFS_TPU_EC_FETCH_DEADLINE", 180.0)


def make_mesh(devices=None):
    """Default rebuild mesh over the available chips: volumes
    data-parallel on "vol", byte columns on "col"."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    col = 2 if n % 2 == 0 else 1
    vol = n // col
    return Mesh(np.array(devices[:vol * col]).reshape(vol, col),
                ("vol", "col"))


@dataclass
class RebuildPlan:
    """Volumes grouped by (codec, survivor signature): every volume in
    a group shares a codec and lost the same shards, so one decode
    matrix (and one compiled step) covers the whole group."""

    groups: dict[tuple[str, tuple[int, ...], tuple[int, ...]],
                 list[tuple[int, dict[int, list[str]]]]] = \
        field(default_factory=dict)
    skipped: list[tuple[int, str]] = field(default_factory=list)


def plan_rebuilds(env, vids=None) -> RebuildPlan:
    """Group rebuildable EC volumes by (codec, present, missing).
    Shard counts and decodability derive from each volume's codec —
    a mixed-codec cluster must never plan an LRC volume with RS
    literals (or vice versa).  Codec ids come from the /vol/list
    payload already in hand (heartbeats put "codec" on every ec_shards
    entry), with env.ec_codec(vid) as the per-volume fallback; a
    volume whose codec cannot be DETERMINED is skipped, never guessed
    — decoding LRC shards with RS matrices would scatter silently
    corrupt bytes cluster-wide."""
    plan = RebuildPlan()
    codecs: dict[int, str] = {}
    try:
        nodes = env.data_nodes()
    except Exception:  # noqa: BLE001 — fall back to per-vid lookups
        nodes = []
    for n in nodes:
        for e in n.get("ec_shards", []):
            if e.get("codec"):
                codecs[e["id"]] = e["codec"]
    if vids is None:
        vids = sorted({e["id"] for n in nodes for e in n["ec_shards"]})
    for vid in vids:
        name = codecs.get(vid)
        if name is None:
            getter = getattr(env, "ec_codec", None)
            if getter is None:  # duck-typed env predating codecs: rs
                name = "rs"
            else:
                try:
                    name = getter(vid) or "rs"
                except Exception as e:  # noqa: BLE001 — master hiccup
                    plan.skipped.append(
                        (vid, f"cannot determine codec: "
                              f"{type(e).__name__}: {e}"))
                    continue
        try:
            codec = get_codec(name)
        except ValueError:
            plan.skipped.append((vid, f"unknown codec {name!r}"))
            continue
        locs = env.ec_shard_locations(vid)
        present = tuple(sorted(locs))
        missing = tuple(s for s in range(codec.total_shards)
                        if s not in locs)
        if not missing:
            continue
        try:
            codec.repair_plan(present, list(missing))
        except ValueError:
            plan.skipped.append(
                (vid, f"only {len(present)} shards survive "
                      f"({codec.name}: unrecoverable pattern)"))
            continue
        plan.groups.setdefault((codec.name, present, missing),
                               []).append((vid, locs))
    return plan


def plan_repair_reads(codec: Codec, present, missing) -> dict:
    """Repair-bandwidth plan for one volume: per-missing-shard minimal
    read sets (local group first, global fallback) plus the
    planned-vs-RS accounting the rebuild reports — RS(k) reads
    data_shards survivors once to rebuild everything, so the saving is
    union-of-planned-reads vs data_shards."""
    plans = codec.repair_plan(tuple(present), list(missing))
    union: set[int] = set()
    for p in plans:
        union.update(p.reads)
    return {
        "codec": codec.name,
        "reads": {p.sid: list(p.reads) for p in plans},
        "union_reads": sorted(union),
        "planned_read_shards": len(union),
        "rs_read_shards": codec.data_shards,
        "local_repairs": sum(1 for p in plans if p.local),
    }


def _fetch_shard(holders: list[str], vid: int, sid: int,
                 attempt_timeout: float | None = None,
                 total_deadline: float | None = None) -> bytes:
    """Fetch one shard, failing over across EVERY holder of it (the
    reference read path walks all sourceDataNodes,
    store_ec.go:264-320) with a second retry round for transient
    errors — one flaky node must not fail a whole batch.

    Every holder attempt runs under `attempt_timeout`, and all attempts
    together under `total_deadline`: a dead holder costs one bounded
    attempt before failover, and a shard with only dead holders fails
    the batch within the deadline instead of hanging it."""
    attempt_timeout = attempt_timeout or FETCH_ATTEMPT_TIMEOUT
    total_deadline = total_deadline or FETCH_TOTAL_DEADLINE
    deadline = time.monotonic() + total_deadline
    errors: list[str] = []
    permanent: set[str] = set()
    for attempt in range(2):
        for url in holders:
            if url in permanent:
                continue
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                errors.append(f"deadline {total_deadline:g}s exhausted")
                raise rpc.RpcError(
                    502, f"shard {vid}.{sid} unreachable within "
                         f"deadline: " + "; ".join(errors[:6]))
            try:
                if _fault.ARMED:
                    _fault.hit("ec.fetch_shard", holder=url, vid=vid,
                               shard=sid)
                data = rpc.call(
                    f"http://{url}/admin/ec/shard_file?volume={vid}"
                    f"&shard={sid}",
                    timeout=min(attempt_timeout, remaining),
                    headers={**rpc.PRIORITY_LOW,
                             **_flows.tag("ec.gather")})
                if not isinstance(data, (bytes, bytearray)):
                    raise rpc.RpcError(
                        410, f"shard {vid}.{sid}: non-binary reply")
                return bytes(data)
            except rpc.RpcError as e:
                # A definitive HTTP answer (4xx: the holder does not
                # have the shard) will not change on a retry — but a
                # 429 admission shed is the holder saying "later", not
                # "never": keep it in the failover rotation.
                if (400 <= e.status < 500 or e.status == 410) \
                        and e.status != 429:
                    permanent.add(url)
                errors.append(f"{url} (try {attempt + 1}): {e}")
            except Exception as e:  # noqa: BLE001 — transient: next
                errors.append(
                    f"{url} (try {attempt + 1}): {type(e).__name__}: {e}")
    raise rpc.RpcError(
        502, f"shard {vid}.{sid} unreachable on any holder: "
             + "; ".join(errors[:6]))


class _TargetPicker:
    """Free-slot balanced placement for rebuilt shards, preferring nodes
    that hold nothing of the volume (maximises survivors on node loss —
    the same objective as balancedEcDistribution)."""

    def __init__(self, env):
        self.free: dict[str, int] = {}
        for n in env.data_nodes():
            held = sum(ShardBits(e["shard_bits"]).shard_id_count()
                       for e in n["ec_shards"])
            free = n["max_volume_count"] * 10 - len(n["volumes"]) * 10 \
                - held
            self.free[n["url"]] = max(free, 0)

    def pick(self, holders: set[str]) -> str:
        if not self.free:
            raise rpc.RpcError(503, "no data nodes for rebuilt shards")
        fresh = {u: f for u, f in self.free.items() if u not in holders}
        pool = fresh if any(f > 0 for f in fresh.values()) else self.free
        url = max(pool, key=lambda u: pool[u])
        self.free[url] -= 1
        return url


def _pad_to(n: int, align: int) -> int:
    return -(-n // align) * align


def batch_rebuild(env, vids=None, mesh=None, max_batch_bytes=1 << 28,
                  workers: int = 16, matrix_kind: str = "vandermonde",
                  progress=None) -> list[str]:
    """Rebuild all missing EC shards across the cluster in mesh-batched
    compiled steps.  Returns one human-readable line per volume.

    env: duck-typed cluster view (shell CommandEnv): ec_shard_locations,
    data_nodes, vs_call.
    """
    plan = plan_rebuilds(env, vids)
    messages = [f"volume {vid}: SKIPPED — {why}; cannot rebuild"
                for vid, why in plan.skipped]
    if not plan.groups:
        return messages
    if mesh is None:
        mesh = make_mesh()
    picker = _TargetPicker(env)
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=workers)
    try:
        for (codec_name, present, missing), entries in \
                sorted(plan.groups.items()):
            messages += _rebuild_group(
                env, mesh, pool, picker, get_codec(codec_name),
                present, missing, entries, max_batch_bytes,
                matrix_kind, progress)
    finally:
        # cancel_futures: a failed group must not leave queued shard
        # fetches/pushes running (and holders busy) after the
        # exception has unwound.
        pool.shutdown(wait=False, cancel_futures=True)
    return messages


def _rebuild_group(env, mesh, pool, picker, codec, present, missing,
                   entries, max_batch_bytes, matrix_kind,
                   progress) -> list[str]:
    """One (codec, survivor-signature) group — journaled as
    ec.rebuild.start/finish with per-stage byte/second attrs plus the
    planner's planned-vs-RS read accounting, under a root span so the
    timeline row links to a /debug/traces trace."""
    vids = [vid for vid, _locs in entries]
    report = plan_repair_reads(codec, present, missing)
    with root_span("ec.batch_rebuild", "ec", volumes=len(vids),
                   missing=list(missing), codec=codec.name):
        emit_event("ec.rebuild.start", volumes=vids, batch=True,
                   missing=list(missing), codec=codec.name,
                   planned_read_shards=report["planned_read_shards"],
                   rs_read_shards=report["rs_read_shards"])
        t0 = time.perf_counter()
        stages: dict[str, list[float]] = {}  # stage -> [seconds, bytes]
        try:
            out = _rebuild_group_inner(env, mesh, pool, picker, codec,
                                       present, missing, entries,
                                       max_batch_bytes, matrix_kind,
                                       progress, stages, report)
        except Exception as e:
            emit_event("ec.rebuild.finish", severity="error",
                       volumes=vids, batch=True, missing=list(missing),
                       codec=codec.name,
                       seconds=round(time.perf_counter() - t0, 6),
                       error=f"{type(e).__name__}: {e}",
                       **stage_attrs(stages))
            raise
        emit_event("ec.rebuild.finish", volumes=vids, batch=True,
                   missing=list(missing), codec=codec.name,
                   planned_read_shards=report["planned_read_shards"],
                   rs_read_shards=report["rs_read_shards"],
                   seconds=round(time.perf_counter() - t0, 6),
                   **stage_attrs(stages))
        return out


def _rebuild_group_inner(env, mesh, pool, picker, codec, present,
                         missing, entries, max_batch_bytes, matrix_kind,
                         progress, stages, report) -> list[str]:
    """Streamed rebuild of one survivor-signature group: the producer
    gathers + stacks the NEXT sub-batch's shards over HTTP while the
    device decodes the current one and the drain thread scatters
    completed shards — gather, decode and scatter overlap instead of
    serializing (stream_pipeline.py; sums of the batch_* stage
    histograms exceed the wall clock when the overlap is working)."""
    from .cluster_encode import PIPELINE_DEPTH, fused_crc_enabled
    # The codec's planned read set, not "first data_shards survivors":
    # an in-group LRC loss gathers 5 shards per volume instead of 10.
    _mat, used = codec.decode_matrix(present, missing)
    all_local = bool(report["local_repairs"]) and \
        report["local_repairs"] == len(missing)
    vol_axis = mesh.shape["vol"]
    col_axis = mesh.shape["col"]
    fused = fused_crc_enabled()
    block = SMALL_BLOCK_SIZE
    align = block * col_axis if fused \
        else _pad_to(_COL_ALIGN, col_axis * 8)
    out: list[str] = []
    saved = f" ({codec.name}: read {len(used)} shards vs " \
            f"{codec.data_shards} for RS)" \
        if len(used) < codec.data_shards else ""

    # Always-on (bounded) production recorder: per-batch stage spans
    # feed the roofline plane's occupancy/gantt surfaces.
    rec = PipelineRecorder(maxlen=1024) if _roofline.ARMED else None

    def produce():
        i = 0
        bi = 0
        while i < len(entries):
            # Probe the first volume's shard size to bound the
            # sub-batch.
            t_gather = time.perf_counter()
            vid0, locs0 = entries[i]
            rows0 = _fetch_rows(pool, vid0, locs0, used)
            shard_bytes = len(rows0[0])
            per_vol = shard_bytes * (len(used) + len(missing))
            chunk_v = max(1, min(len(entries) - i,
                                 int(max_batch_bytes
                                     // max(per_vol, 1))))
            chunk = entries[i:i + chunk_v]
            # Flat fan-out of every (volume, shard) fetch — nested
            # submits from inside pool workers would deadlock a
            # bounded pool.
            futs = [[pool.submit(_fetch_shard, locs[sid], vid, sid)
                     for sid in used] for vid, locs in chunk[1:]]
            fetched = [rows0] + [[f.result() for f in row]
                                 for row in futs]
            gathered = sum(len(row) for rows in fetched for row in rows)
            ec_repair_read_bytes_total.inc(gathered, codec=codec.name)
            sizes = [len(rows[0]) for rows in fetched]
            n_pad = _pad_to(max(sizes), align)
            v_pad = _pad_to(len(chunk), vol_axis)
            stacked = np.zeros((v_pad, len(used), n_pad), np.uint8)
            for v, rows in enumerate(fetched):
                for r, row in enumerate(rows):
                    if len(row) != sizes[v]:
                        raise rpc.RpcError(
                            502, f"volume {chunk[v][0]}: survivor "
                            f"shards disagree on size "
                            f"({len(row)} vs {sizes[v]})")
                    stacked[v, r, :len(row)] = np.frombuffer(row,
                                                             np.uint8)
            t_gend = time.perf_counter()
            observe_batch_stage(stages, "batch_gather",
                                t_gend - t_gather, gathered)
            if rec is not None:
                rec.note_span("stack", bi, t_gather, t_gend)
            yield (stacked, chunk, sizes, bi)
            bi += 1
            i += chunk_v

    def dispatch(item):
        stacked, chunk, sizes, bi = item
        t_d0 = time.perf_counter()
        # Device CRCs for the rebuilt rows ride along when every shard
        # in the sub-batch covers whole `.ecc` blocks (they always do:
        # shard files are 1MB-block padded by construction).
        use_crc = fused and all(s % block == 0 for s in sizes)
        if use_crc:
            rebuilt, crcs = batched_reconstruct_with_crc(
                stacked, present, missing, mesh, codec=codec)
        else:
            rebuilt = batched_reconstruct(
                stacked, present, missing, mesh,
                matrix_kind=matrix_kind, codec=codec)
            crcs = None
        t_d1 = time.perf_counter()
        if rec is not None:
            rec.note_span("dispatch", bi, t_d0, t_d1)
        return (rebuilt, crcs, chunk, sizes, stacked.nbytes, bi,
                t_d0, t_d1)

    def drain(handle):
        rebuilt, crcs, chunk, sizes, nbytes, bi, t_d0, t_d1 = handle
        # np.asarray fences the dispatch — the EXPOSED device wait.
        t_dev = time.perf_counter()
        rebuilt = np.asarray(rebuilt)
        if crcs is not None:
            crcs = np.asarray(crcs)
        t_fence = time.perf_counter()
        observe_batch_stage(stages, "batch_rebuild_device",
                            t_fence - t_dev, nbytes)
        if rec is not None:
            rec.note_span("device", bi, t_d1, t_fence)
        if _roofline.ARMED:
            record_fenced_batch(
                "batch_reconstruct", codec.name,
                out_rows=int(rebuilt.shape[1]),
                in_rows=len(used), n=int(rebuilt.shape[2]),
                batch=int(rebuilt.shape[0]), crc=crcs is not None,
                seconds=t_fence - t_d0,
                measured_bytes=int(nbytes) + rebuilt.nbytes)
        t_scatter = time.perf_counter()
        scattered = 0
        for v, (vid, locs) in enumerate(chunk):
            shards = [rebuilt[v, m, :sizes[v]].tobytes()
                      for m in range(len(missing))]
            scattered += sum(len(s) for s in shards)
            shard_crcs = None
            if crcs is not None:
                nb = sizes[v] // block
                shard_crcs = [[int(c) for c in crcs[v, m, :nb]]
                              for m in range(len(missing))]
            placed = _scatter_volume(
                env, pool, picker, vid, locs, missing, shards,
                shard_crcs=shard_crcs)
            if all_local:
                emit_event("ec.repair.local", vid=vid,
                           codec=codec.name, shard=list(missing),
                           reads=len(used),
                           bytes=sizes[v] * len(used))
            out.append(f"volume {vid}: rebuilt shards "
                       f"{list(missing)} -> " +
                       ", ".join(f"{s}@{u}" for s, u in placed)
                       + saved)
            if progress:
                progress(out[-1])
        t_send = time.perf_counter()
        observe_batch_stage(stages, "batch_scatter",
                            t_send - t_scatter, scattered)
        if rec is not None:
            rec.note_span("drain", bi, t_scatter, t_send)

    run_pipeline(produce(), dispatch, drain, depth=PIPELINE_DEPTH,
                 recorder=rec)
    if rec is not None:
        _roofline.LEDGER.note_pipeline("rebuild", rec)
    return out


def _fetch_rows(pool, vid, locs, used) -> list[bytes]:
    """Parallel-fetch the `used` survivor shards of one volume (each
    failing over across its holders) — the client-side analog of the
    reference's parallel shard reads (store_ec.go:322-376)."""
    futs = [pool.submit(_fetch_shard, locs[sid], vid, sid)
            for sid in used]
    return [f.result() for f in futs]


def _push_shard(vid: int, sid: int, payload: bytes, target: str,
                sources: list[str], ecc_push=None) -> None:
    """Push one rebuilt shard; the target pulls the .ecx index from a
    source holder, so fail over across sources — a stale/dead entry in
    the location map must not sink the scatter."""
    if ecc_push is not None:
        # Ship the target its kernel-computed `.ecc` entries before the
        # first shard body lands (once per target, inside this worker —
        # a slow target can't stall the drain thread; cluster_encode.
        # _EccOncePush).
        ecc_push.ensure(target)
    errors: list[str] = []
    for src in sources:
        try:
            if _fault.ARMED:
                _fault.hit("ec.scatter", target=target, vid=vid,
                           shard=sid)
            rpc.call(
                f"http://{target}/admin/ec/receive_shard?volume={vid}"
                f"&shard={sid}&ecx_source={src}",
                "POST", payload, 600.0,
                headers={**rpc.PRIORITY_LOW,
                         **_flows.tag("ec.scatter")})
            return
        except rpc.RpcError as e:
            # The target responded: the failure may be its ecx pull
            # from this source — another source can fix that.
            errors.append(f"via {src}: {e}")
        except Exception as e:
            # Can't reach the target at all: no ecx_source choice will
            # help, and re-sending the full shard payload per source
            # would multiply a dead node into hours of timeouts.
            raise rpc.RpcError(
                502, f"cannot place rebuilt shard {vid}.{sid}: target "
                     f"{target} unreachable: {type(e).__name__}: {e}"
            ) from None
    raise rpc.RpcError(
        502, f"cannot place rebuilt shard {vid}.{sid} on {target}: "
             + "; ".join(errors[:4]))


def _scatter_volume(env, pool, picker, vid, locs, missing,
                    shards: list[bytes],
                    shard_crcs=None) -> list[tuple[int, str]]:
    """Push rebuilt shards to balanced targets, pulling the .ecx index
    alongside, then mount.  When `shard_crcs` carries the device-
    computed per-block CRC32-C of each rebuilt shard, the target gets
    its `.ecc` entries FIRST so receive_shard skips the CPU re-read of
    the pushed payload (and wire corruption of the push itself is
    scrub-detectable)."""
    holders = {u for urls in locs.values() for u in urls}
    sources = sorted(holders)
    placed = [(sid, picker.pick(holders)) for sid in missing]
    pusher = None
    if shard_crcs is not None:
        from .cluster_encode import _ecc_push_plan
        pusher = _ecc_push_plan(
            vid, ((target, sid, crcs)
                  for (sid, target), crcs in zip(placed, shard_crcs)))
    futs = [pool.submit(_push_shard, vid, sid, payload, target,
                        sources, pusher)
            for (sid, target), payload in zip(placed, shards)]
    for f in futs:
        f.result()
    for _sid, target in placed:
        env.vs_call(target, "/admin/ec/mount", {"volume": vid})
    return placed
