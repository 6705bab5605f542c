#!/usr/bin/env python3
"""chip_smoke.py — does the served EC path still start on the chip, and
is what it writes right?

Two legs run one after the other, each with exactly ONE chip-owning
process.  This launcher never imports JAX.

  served  `python -m seaweedfs_tpu server` (master + volume server in one
          process: the chip owner of the cluster -> chip map in
          seaweedfs_tpu/utils/jaxenv.py), driven from here over HTTP and
          the admin shell: put, get, `ec.encode`, reads through EC, two
          shards lost, degraded reads, `ec.rebuild`.
  batch   one child process holding an in-process master + 3 volume
          servers + shell env (the `__graft_entry__.dryrun_multichip`
          layout): `ec.encode -batch`, shards lost, `ec.rebuild -batch`
          over a mesh of every local device — the XLA path.

Every shard file is checked against the plain reference: parity equals
`NumpyCoder` parity of the data shards on sampled 1 MiB blocks, every
`.ecc` entry equals `file_block_crcs` of its shard, rebuilt shards equal
the bytes that were deleted, needles read back equal what was put.  Data
comes from --seed.  No SEAWEEDFS_TPU_* variable is set: defaults are
what is under test.

Exit 0, and a last stdout line {"ok": true, "device": {...}}, only when
every check of both legs passed ON A TPU.  No TPU, a failed check, a
leg that resolved a non-TPU coder, or a child that dies: non-zero exit
and no result line.

`--rehearse-cpu` runs the same command path at tiny sizes on the CPU
platform (Pallas in interpret mode) so the script can be debugged where
there is no chip.  It prints platform=cpu and never an "ok" line.

reduced: upstream seals 30 GB volumes; the served leg seals 1 GiB, so no
1 GB large-block row is exercised (the kernel sees the same (10, 4 MiB)
chunk either way; ROADMAP B1 covers the row path).

A machine may cap the size of one file (RLIMIT_FSIZE, or the file
system's own limit): a write past it is EFBIG.  The launcher measures
the cap of its work directory first, the way an operator sizes
`-volumeSizeLimitMB` to the host.  Where a 1 GiB volume does not fit,
the served leg fills, seals, degrades and rebuilds as many smaller
volumes as hold 1 GiB together, and the batch leg's volumes shrink by
whole 4 MiB chunks; the result line names the cap among `reduced`.
"""

from __future__ import annotations

import argparse
import errno
import hashlib
import json
import os
import re
import resource
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

from seaweedfs_tpu.cluster import rpc  # noqa: E402
from seaweedfs_tpu.cluster.client import WeedClient  # noqa: E402
from seaweedfs_tpu.core import types as wt  # noqa: E402
from seaweedfs_tpu.core.crc import crc32c  # noqa: E402
from seaweedfs_tpu.ec import (DATA_SHARDS, PARITY_SHARDS,  # noqa: E402
                              SMALL_BLOCK_SIZE, TOTAL_SHARDS, to_ext)
from seaweedfs_tpu.ec.integrity import (ShardChecksums,  # noqa: E402
                                        file_block_crcs)
from seaweedfs_tpu.ec.volume import EcVolume  # noqa: E402
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder  # noqa: E402
from seaweedfs_tpu.shell import CommandEnv, run_command  # noqa: E402
from seaweedfs_tpu.utils import native  # noqa: E402

MIB = 1 << 20

# (full, rehearsal)
SERVED_BYTES = (1024 * MIB, 24 * MIB)
BATCH_VOLUMES = 4
# Per volume.  272 MiB, not 256: it fills 28 ten-MiB stripe rows, i.e.
# seven whole 4 MiB chunks, so every mesh step of the seal has ONE shape
# — each further shape is another XLA compile of a minute or more.
BATCH_BYTES = (272 * MIB, 6 * MIB)
BATCH_CHUNK_BYTES = 40 * MIB             # (10, 4 MiB): one encode chunk
NEEDLE_RANGE = ((4096, 4 * MIB), (4096, MIB))
# What a volume file holds beyond the bytes asked for: the last needle
# (sizes sum to >= the target), needle headers and padding.
VOLUME_SLACK = (16 * MIB, 2 * MIB)
# Below this the batch leg has no whole chunk to seal and too few
# needles cross one shard for the degraded reads: the smoke fails
# instead of shrinking further.
MIN_VOLUME_BYTES = (40 * MIB, 12 * MIB)
# Either leg has at most 2.6 GiB on disk at once: volumes + their shards.
WORK_FREE_BYTES = (4 << 30, 256 * MIB)
SERVED_LOST = (3, 11)                    # one data, one parity
BATCH_LOST = (1, 12)
# `-maxBatchMB` for the batch leg's `ec.encode`: above 4 x 272 MiB, so
# that all four volumes stack on the mesh's "vol" axis in one group (the
# default 256 seals them one by one).  `ec.rebuild -batch` keeps its
# default, which decodes one 28 MiB-shard volume per step.
BATCH_ENCODE_MAX_MB = 2048
# One held step of the batch kernel: (BATCH_VOLUMES, 10, width) — the
# shape of the batch leg's own full encode step.
MESH_STEP_WIDTH = (4 * MIB, 2 * MIB)
SAMPLE_NEEDLES = 12
MIN_CROSSING = (3, 1)       # needles on the lost data shard, of 6 read
SAMPLE_BLOCKS = 4


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the machine: where the work directory goes and how large a file may be
# ---------------------------------------------------------------------------

# What a refused run needs to be understood from the end of its standard
# error alone; printed after the traceback of whatever failed.
MACHINE: dict = {}


def _excepthook(tp, val, tb) -> None:
    sys.__excepthook__(tp, val, tb)
    print("chip_smoke machine: " + json.dumps(MACHINE), file=sys.stderr,
          flush=True)


def file_size_cap(directory: str, want: int) -> int:
    """The largest file, up to `want` bytes, that `directory` holds,
    within 1 MiB: the process's RLIMIT_FSIZE or the file system's own
    limit, found by growing a sparse file (nothing is written)."""
    fd, path = tempfile.mkstemp(dir=directory, prefix="chip_smoke_cap_")
    try:
        def fits(n: int) -> bool:
            try:
                os.ftruncate(fd, n)
            except OSError as e:
                if e.errno != errno.EFBIG:
                    raise
                return False
            return True

        if fits(want):
            return want
        lo, hi = 0, want
        while hi - lo > MIB:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if fits(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)
        os.unlink(path)


def place_work_dir(rehearse: bool) -> tuple[str, int]:
    """(work directory, bytes of needles one volume file there may be
    asked to hold).  The system's temp directory unless it cannot hold a
    full-size volume and /dev/shm can."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    MACHINE["rlimit_fsize"] = [soft, hard]
    want = SERVED_BYTES[rehearse] + VOLUME_SLACK[rehearse]
    found = []
    for d in dict.fromkeys((tempfile.gettempdir(), "/dev/shm")):
        if os.path.isdir(d) and os.access(d, os.W_OK):
            found.append((d, file_size_cap(d, want),
                          shutil.disk_usage(d).free))
    MACHINE["dirs"] = found
    roomy = [f for f in found if f[2] >= WORK_FREE_BYTES[rehearse]]
    check(roomy, f"no directory with {WORK_FREE_BYTES[rehearse]} bytes "
                 f"free: {found}")
    d, cap, _free = max(roomy, key=lambda f: f[1])   # first of the largest
    MACHINE["work_parent"], MACHINE["file_cap"] = d, cap
    check(cap - VOLUME_SLACK[rehearse] >= MIN_VOLUME_BYTES[rehearse],
          f"a file in {d} may hold {cap} bytes: too small for a volume")
    return (tempfile.mkdtemp(prefix="chip_smoke_", dir=d),
            cap - VOLUME_SLACK[rehearse])


def batch_volume_bytes(rehearse: bool, volume_cap: int) -> int:
    """BATCH_BYTES, or where a file may not be that large the most whole
    encode chunks that fit: still one shape per mesh step."""
    if volume_cap >= BATCH_BYTES[rehearse]:
        return BATCH_BYTES[rehearse]
    chunks = (volume_cap + 8 * MIB) // BATCH_CHUNK_BYTES
    check(chunks >= 1 and not rehearse,
          f"batch: no whole chunk fits a volume of {volume_cap} bytes")
    return chunks * BATCH_CHUNK_BYTES - 8 * MIB


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def needle_sizes(seed: int, stream: int, lo: int, hi: int,
                 total: int) -> list[int]:
    """Log-uniform needle sizes in [lo, hi] summing to >= total."""
    rng = np.random.default_rng([seed, stream])
    out, acc = [], 0
    while acc < total:
        n = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        out.append(n)
        acc += n
    return out


def needle_payload(seed: int, stream: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, stream, i]).bytes(size)


def put_needles(client: WeedClient, seed: int, stream: int,
                sizes: list[int], collection: str = "") -> list[tuple]:
    """Upload needle i of `stream` for every size; returns
    [(fid, i, size)].  Payloads are regenerated from the seed when read
    back, never held."""
    out = []
    for i, size in enumerate(sizes):
        r = client.upload(needle_payload(seed, stream, i, size),
                          collection=collection, compress=False)
        out.append((r["fid"], i, size))
    return out


def read_back(client: WeedClient, seed: int, stream: int,
              needles: list[tuple], what: str) -> None:
    for fid, i, size in needles:
        got = bytes(client.download(fid))
        check(got == needle_payload(seed, stream, i, size),
              f"{what}: needle {fid} ({size} B) read back differs")


def sample(seed: int, stream: int, items: list, k: int) -> list:
    rng = np.random.default_rng([seed, stream, 9])
    idx = rng.choice(len(items), size=min(k, len(items)), replace=False)
    return [items[int(j)] for j in sorted(idx)]


# ---------------------------------------------------------------------------
# checks against the plain reference
# ---------------------------------------------------------------------------

def check_shard_set(paths: dict[int, str], ecc_of: dict[int, str],
                    ecx: str, seed: int, what: str) -> dict:
    """All shard files + .ecx exist and agree in size; parity shards
    equal NumpyCoder parity of the data shards on sampled 1 MiB blocks;
    every `.ecc` entry equals file_block_crcs of its shard.  `paths`
    maps shard id -> file, `ecc_of` shard id -> the base path whose
    `.ecc` sidecar covers it."""
    check(sorted(paths) == list(range(TOTAL_SHARDS)),
          f"{what}: shard ids {sorted(paths)}")
    for sid, p in paths.items():
        check(os.path.exists(p), f"{what}: missing {p}")
    check(os.path.exists(ecx), f"{what}: missing {ecx}")
    size = os.path.getsize(paths[0])
    check(size > 0 and size % SMALL_BLOCK_SIZE == 0,
          f"{what}: shard size {size}")
    for sid, p in paths.items():
        check(os.path.getsize(p) == size,
              f"{what}: shard {sid} size differs")
    nblocks = size // SMALL_BLOCK_SIZE
    rng = np.random.default_rng([seed, 77])
    blocks = sorted({0, nblocks - 1, *(int(b) for b in rng.integers(
        0, nblocks, SAMPLE_BLOCKS))})
    oracle = NumpyCoder(DATA_SHARDS, PARITY_SHARDS)
    for b in blocks:
        rows = []
        for sid in range(TOTAL_SHARDS):
            with open(paths[sid], "rb") as f:
                f.seek(b * SMALL_BLOCK_SIZE)
                rows.append(np.frombuffer(f.read(SMALL_BLOCK_SIZE),
                                          np.uint8))
        want = oracle.encode(np.stack(rows[:DATA_SHARDS]))
        check(np.array_equal(want, np.stack(rows[DATA_SHARDS:])),
              f"{what}: parity != NumpyCoder reference in block {b}")
    for sid in range(TOTAL_SHARDS):
        got = ShardChecksums.load(ecc_of[sid]).get(sid)
        check(got is not None and got == file_block_crcs(paths[sid]),
              f"{what}: .ecc of shard {sid} != file_block_crcs")
    return {"shard_bytes": size, "parity_blocks_checked": len(blocks),
            "ecc_entries_checked": TOTAL_SHARDS * nblocks}


def files_equal(a: str, b: str) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while chunk := fa.read(8 * MIB):
            if chunk != fb.read(len(chunk)):
                return False
    return True


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def child_env(rehearse: bool) -> dict:
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_LOG_COMPILES="1")
    if rehearse:
        # The CPU platform would resolve the native coder; the
        # rehearsal is of the chip's code path, so ask for it.
        env.update(JAX_PLATFORMS="cpu", SEAWEEDFS_TPU_CODER="pallas",
                   SEAWEEDFS_TPU_EC_FUSED_CRC="1")
    return env


_COMPILE = re.compile(
    r"Finished XLA compilation of jit\((.+?)\) in ([0-9.eE+-]+) sec")
_LOWER = re.compile(
    r"Finished (?:tracing \+ transforming|jaxpr to MLIR module "
    r"conversion) .* in ([0-9.eE+-]+) sec")
_DEVICE = re.compile(
    r"(\S+) device: coder=(\S+) platform=(\S+) device_kind='([^']*)' "
    r"devices=(\d+)")


def compile_stats(log_path: str) -> dict:
    """What JAX_LOG_COMPILES=1 made the child say: how many XLA
    compilations, the seconds they and their tracing/lowering took, and
    the costliest by jitted name (a persistent-cache hit still logs,
    with the short time it took)."""
    by_name: dict[str, list] = {}
    lower = 0.0
    with open(log_path, errors="replace") as f:
        for line in f:
            m = _COMPILE.search(line)
            if m:
                ent = by_name.setdefault(m.group(1), [0, 0.0])
                ent[0] += 1
                ent[1] += float(m.group(2))
                continue
            m = _LOWER.search(line)
            if m:
                lower += float(m.group(1))
    top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:6]
    return {"compilations": sum(c for c, _s in by_name.values()),
            "xla_compile_seconds":
                round(sum(s for _c, s in by_name.values()), 3),
            "trace_lower_seconds": round(lower, 3),
            "costliest": {k: [c, round(s, 3)] for k, (c, s) in top}}


def stop_child(p: subprocess.Popen) -> None:
    if p.poll() is None:
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=40)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10)


def log_tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


# ---------------------------------------------------------------------------
# served leg
# ---------------------------------------------------------------------------

def wait_for_server(p: subprocess.Popen, log_path: str, master: str,
                    timeout: float = 180.0) -> dict:
    """The start-up device line, then a registered data node."""
    deadline = time.monotonic() + timeout
    resolved = None
    while time.monotonic() < deadline:
        check(p.poll() is None,
              f"server exited with {p.returncode}:\n{log_tail(log_path)}")
        if resolved is None:
            with open(log_path, errors="replace") as f:
                m = _DEVICE.search(f.read())
            if m:
                resolved = {"role": m.group(1), "coder": m.group(2),
                            "platform": m.group(3),
                            "device_kind": m.group(4),
                            "count": int(m.group(5))}
        else:
            try:
                st = rpc.call(f"{master}/dir/status", timeout=2.0)
                if st.get("topology", {}).get("children"):
                    return resolved
            except (rpc.RpcError, OSError):
                pass
        time.sleep(0.2)
    raise SmokeFailure(
        f"server not ready in {timeout:.0f}s:\n{log_tail(log_path)}")


def served_volume(master: str, vport: int, data_dir: str, keep_dir: str,
                  env: CommandEnv, seed: int, rehearse: bool, part: int,
                  nbytes: int) -> dict:
    """Fill one volume with `nbytes` of needles, then: get, seal, read
    through EC, lose one data and one parity shard, read degraded,
    rebuild, compare."""
    tag = f"served[{part}]"
    stream = 100 + part
    coll = f"smoke{part}"

    # -- put, get -------------------------------------------------------
    rpc.call_json(f"{master}/vol/grow?count=1&collection={coll}", "POST")
    client = WeedClient(master)
    lo, hi = NEEDLE_RANGE[rehearse]
    sizes = needle_sizes(seed, stream, lo, hi, nbytes)
    t0 = time.perf_counter()
    needles = put_needles(client, seed, stream, sizes, coll)
    t_put = time.perf_counter() - t0
    vids = {wt.parse_file_id(fid)[0] for fid, _i, _s in needles}
    check(len(vids) == 1, f"{tag}: uploads landed in volumes {vids}")
    vid = vids.pop()
    picked = sample(seed, stream, needles, SAMPLE_NEEDLES)
    read_back(client, seed, stream, picked, f"{tag} plain read")
    say(f"{tag}: put {len(needles)} needles, {sum(sizes) / MIB:.0f} MiB "
        f"into volume {vid} in {t_put:.1f}s; read {len(picked)} back")

    # -- seal -------------------------------------------------------------
    base = os.path.join(data_dir, f"{coll}_{vid}")
    dat_bytes = os.path.getsize(base + ".dat")
    t0 = time.perf_counter()
    out = run_command(env, f"ec.encode -volumeId {vid}")
    t_seal = time.perf_counter() - t0
    check(f"volume {vid} -> ec shards" in out, f"{tag}: {out}")
    paths = {sid: base + to_ext(sid) for sid in range(TOTAL_SHARDS)}
    facts = check_shard_set(paths, dict.fromkeys(paths, base),
                            base + ".ecx", seed, f"{tag} seal")
    client = WeedClient(master)         # no cached pre-seal locations
    read_back(client, seed, stream, picked, f"{tag} read through EC")
    say(f"{tag}: ec.encode of {dat_bytes / MIB:.0f} MiB took "
        f"{t_seal:.1f}s; shards + .ecc match the reference ({facts}); "
        f"{len(picked)} needles read through EC")

    # -- lose one data and one parity shard ---------------------------------
    ev = EcVolume(base, vid=vid)
    try:
        lost_data = SERVED_LOST[0]
        crossing = [
            n for n in needles
            if any(iv.to_shard_id_and_offset(
                ev.large_block_size, ev.small_block_size)[0] == lost_data
                for iv in ev.locate_needle(
                    wt.parse_file_id(n[0])[1])[2])]
    finally:
        ev.close()
    check(len(crossing) >= MIN_CROSSING[rehearse],
          f"{tag}: only {len(crossing)} needles cross shard {lost_data}")
    degraded = sample(seed, stream + 50, crossing, 6)
    kept = {}
    for sid in SERVED_LOST:
        kept[sid] = os.path.join(keep_dir, f"kept_{vid}{to_ext(sid)}")
        shutil.copyfile(paths[sid], kept[sid])
    rpc.call_json(
        f"http://127.0.0.1:{vport}/admin/ec/delete_shards", "POST",
        {"volume": vid, "shards": list(SERVED_LOST)})
    for sid in SERVED_LOST:
        check(not os.path.exists(paths[sid]),
              f"{tag}: shard {sid} still on disk")
    t0 = time.perf_counter()
    read_back(client, seed, stream, degraded, f"{tag} degraded read")
    t_degraded = time.perf_counter() - t0
    say(f"{tag}: {len(degraded)} degraded reads across lost shard "
        f"{lost_data} in {t_degraded:.2f}s")

    # -- rebuild ------------------------------------------------------------
    t0 = time.perf_counter()
    out = run_command(env, f"ec.rebuild -volumeId {vid}")
    t_rebuild = time.perf_counter() - t0
    check("rebuilt shards" in out, f"{tag}: {out}")
    for sid in SERVED_LOST:
        check(files_equal(paths[sid], kept[sid]),
              f"{tag}: rebuilt shard {sid} != the deleted bytes")
        check(ShardChecksums.load(base).get(sid)
              == file_block_crcs(paths[sid]),
              f"{tag}: .ecc of rebuilt shard {sid}")
        os.unlink(kept[sid])
    read_back(client, seed, stream, picked, f"{tag} read after rebuild")
    say(f"{tag}: ec.rebuild of shards {list(SERVED_LOST)} took "
        f"{t_rebuild:.1f}s; rebuilt files equal the deleted bytes")
    return {"volume_bytes": dat_bytes, "needles": len(needles),
            "seal_seconds": t_seal, "rebuild_seconds": t_rebuild,
            "degraded_read_seconds": t_degraded, **facts}


def served_leg(work: str, seed: int, rehearse: bool,
               volume_cap: int) -> dict:
    t_leg = time.perf_counter()
    want_platform = "cpu" if rehearse else "tpu"
    data_dir = os.path.join(work, "served", "data")
    os.makedirs(data_dir)
    log_path = os.path.join(work, "served", "server.log")
    # One volume, unless a file here may not hold it: then as many
    # equal ones as hold the same bytes together.
    total = SERVED_BYTES[rehearse]
    parts = -(-total // min(total, volume_cap))
    mport, vport = rpc.free_port(), rpc.free_port()
    master = f"http://127.0.0.1:{mport}"
    with open(log_path, "wb") as log:
        p = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", "server",
             f"-dir={data_dir}", f"-mdir={os.path.join(work, 'served')}",
             f"-master.port={mport}", f"-volume.port={vport}"],
            env=child_env(rehearse), cwd=ROOT, stdout=log,
            stderr=subprocess.STDOUT)
    try:
        resolved = wait_for_server(p, log_path, master)
        say(f"served: server up in "
            f"{time.perf_counter() - t_leg:.1f}s, resolved {resolved}; "
            f"{parts} volume(s) of {-(-total // parts) / MIB:.0f} MiB")
        check(resolved["platform"] == want_platform
              and resolved["coder"] == "pallas",
              f"served: server resolved {resolved}, wanted the pallas "
              f"coder on {want_platform}")
        env = CommandEnv(master)
        run_command(env, "lock")
        vols = [served_volume(master, vport, data_dir,
                              os.path.join(work, "served"), env, seed,
                              rehearse, part, -(-total // parts))
                for part in range(parts)]
        env.close()

        def summed(key: str):
            return sum(v[key] for v in vols)

        # -- the device did it ------------------------------------------------
        dev = rpc.call(f"http://127.0.0.1:{vport}/debug/device")
        platforms = {d["platform"] for d in dev["devices"]}
        check(platforms == {want_platform},
              f"served: /debug/device platforms {platforms}")
        rows, calls, kernel_seconds = {}, {}, {}
        for r in dev["kernels"]:
            rows[r["kernel"]] = rows.get(r["kernel"], 0) + r["bytes"]
            calls[r["kernel"]] = calls.get(r["kernel"], 0) + r["count"]
            kernel_seconds[r["kernel"]] = round(
                kernel_seconds.get(r["kernel"], 0.0) + r["seconds"], 3)
        # The seal's pipeline drains later, so it calls the coder
        # unfenced and leaves no `encode_crc_kernel` row: what it
        # leaves are its stage rows and one `seal_inflight` count per
        # chunk it drained.
        check(rows.get("seal.dispatch", 0) >= summed("volume_bytes")
              and rows.get("seal.drain", 0)
              >= summed("volume_bytes") * (TOTAL_SHARDS - DATA_SHARDS)
              // DATA_SHARDS,
              f"served: the seal's stage rows {rows} do not cover the "
              f"{summed('volume_bytes')} B of volumes")
        check("encode_crc_kernel" not in rows,
              f"served: a seal left a kernel row of an unfenced wall: "
              f"{rows}")
        drained = sum(dev["seal_inflight"].values())
        check(drained == calls["seal.drain"] > 0,
              f"served: seal_inflight {dev['seal_inflight']} against "
              f"{calls['seal.drain']} drains")
        # The rebuild runs on the same pipeline, unfenced too: the
        # survivors it dispatched, the rebuilt rows it drained and one
        # `rebuild_inflight` count per chunk.
        check(rows.get("rebuild.dispatch", 0)
              >= summed("shard_bytes") * DATA_SHARDS
              and rows.get("rebuild.drain", 0)
              >= summed("shard_bytes") * len(SERVED_LOST),
              f"served: the rebuild's stage rows {rows} do not cover "
              f"the rebuilt shards")
        drained = sum(dev["rebuild_inflight"].values())
        check(drained == calls["rebuild.drain"] > 0,
              f"served: rebuild_inflight {dev['rebuild_inflight']} "
              f"against {calls['rebuild.drain']} drains")
        # ... and the degraded reads went through the third rung's one
        # unfenced call a launch (ec/degraded.py): its stage rows, as
        # many dispatches as drains, every rebuilt byte drained.
        check(calls.get("read.dispatch", 0) == calls.get("read.drain", 0)
              > 0 and calls.get("read.degraded", 0) > 0
              and rows.get("read.drain", 0) == rows.get("read.interval", 0)
              > 0,
              f"served: the degraded reads left no rows of the third "
              f"rung: {calls}")
        check(dev["conservation"]["ok"], f"served: {dev['conservation']}")
    finally:
        stop_child(p)
    check(p.returncode == 0,
          f"served: server exited with {p.returncode}:\n"
          f"{log_tail(log_path)}")
    return {"pass": True, "resolved": resolved,
            "seconds": round(time.perf_counter() - t_leg, 1),
            "volumes": parts,
            "volume_bytes": summed("volume_bytes"),
            "needles": summed("needles"),
            "seal_seconds": round(summed("seal_seconds"), 2),
            "seal_MBps": round(summed("volume_bytes")
                               / summed("seal_seconds") / 1e6, 1),
            "rebuild_seconds": round(summed("rebuild_seconds"), 2),
            "degraded_read_seconds":
                round(summed("degraded_read_seconds"), 3),
            "shard_bytes": summed("shard_bytes"),
            "parity_blocks_checked": summed("parity_blocks_checked"),
            "ecc_entries_checked": summed("ecc_entries_checked"),
            "degraded_read_launches": calls["read.dispatch"],
            "kernel_bytes": rows,
            # fenced walls of the coder calls: H2D + kernel + D2H
            "kernel_call_seconds": kernel_seconds,
            "compile": compile_stats(log_path)}


# ---------------------------------------------------------------------------
# batch leg: parent side
# ---------------------------------------------------------------------------

def batch_leg(work: str, seed: int, rehearse: bool,
              volume_cap: int) -> dict:
    t_leg = time.perf_counter()
    os.makedirs(os.path.join(work, "batch"))
    log_path = os.path.join(work, "batch", "child.log")
    out_path = os.path.join(work, "batch", "report.json")
    argv = [sys.executable, os.path.abspath(__file__), "--seed",
            str(seed), "--batch-child", out_path, "--batch-bytes",
            str(batch_volume_bytes(rehearse, volume_cap))]
    if rehearse:
        argv.append("--rehearse-cpu")
    with open(log_path, "wb") as log:
        p = subprocess.Popen(argv, env=child_env(rehearse), cwd=ROOT,
                             stdout=log, stderr=subprocess.STDOUT)
    try:
        p.wait()
    finally:
        stop_child(p)
    with open(log_path, errors="replace") as f:
        for line in f:
            if line.startswith("batch: "):
                say(line.rstrip())
    check(p.returncode == 0,
          f"batch: child exited with {p.returncode}:\n"
          f"{log_tail(log_path, 60)}")
    with open(out_path) as f:
        report = json.load(f)
    report["seconds"] = round(time.perf_counter() - t_leg, 1)
    report["compile"] = compile_stats(log_path)
    return report


# ---------------------------------------------------------------------------
# batch leg: the child (the only code here that touches JAX)
# ---------------------------------------------------------------------------

def batch_child(out_path: str, seed: int, rehearse: bool,
                per_vol: int) -> int:
    from seaweedfs_tpu.utils import jaxenv
    jaxenv.place_compile_cache()
    import jax

    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.ops.erasure import describe_backend
    from seaweedfs_tpu.parallel.cluster_rebuild import make_mesh
    from seaweedfs_tpu.parallel.sharded_codec import (
        batched_encode_with_crc)
    from seaweedfs_tpu.stats import roofline

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    resolved = describe_backend()
    say(f"batch: {device}; {resolved}")
    check(device["platform"] == ("cpu" if rehearse else "tpu"),
          f"batch: JAX resolved {device}")
    mesh = make_mesh()
    mesh_shape = {k: int(v) for k, v in mesh.shape.items()}
    check(mesh_shape["vol"] * mesh_shape["col"] == len(devs),
          f"batch: mesh {mesh_shape} leaves devices out")

    # Link observation: one 40 MiB encode chunk each way.
    chunk = np.random.default_rng([seed, 5]).integers(
        0, 256, (DATA_SHARDS, 4 * MIB), dtype=np.uint8)
    h2d, d2h = [], []
    for _ in range(4):
        t0 = time.perf_counter()
        on_dev = jax.block_until_ready(jax.device_put(chunk, devs[0]))
        t1 = time.perf_counter()
        back = np.asarray(on_dev)
        t2 = time.perf_counter()
        h2d.append(chunk.nbytes / (t1 - t0) / 1e6)
        d2h.append(chunk.nbytes / (t2 - t1) / 1e6)
    check(np.array_equal(back, chunk), "batch: link round trip differs")
    link = {"h2d_MBps": round(float(np.median(h2d[1:])), 1),
            "d2h_MBps": round(float(np.median(d2h[1:])), 1)}

    # One step of the batch kernel, held, on data from the seed alone
    # (shard FILES carry random needle cookies, so only this digest can
    # match byte for byte between a one-chip and a four-chip run): its
    # outputs sit on every device of the mesh and equal the reference.
    stack = np.random.default_rng([seed, 6]).integers(
        0, 256, (BATCH_VOLUMES, DATA_SHARDS, MESH_STEP_WIDTH[rehearse]),
        dtype=np.uint8)
    t0 = time.perf_counter()
    parity, crcs = batched_encode_with_crc(stack, mesh)
    placed = {s.device for s in parity.addressable_shards}
    check(placed == set(devs),
          f"batch: output shards on {len(placed)} of {len(devs)} devices")
    parity, crcs = np.asarray(parity), np.asarray(crcs)
    oracle = NumpyCoder(DATA_SHARDS, PARITY_SHARDS)
    check(np.array_equal(parity[-1, :, -65536:],
                         oracle.encode(stack[-1, :, -65536:])),
          "batch: mesh step parity != NumpyCoder reference")
    check([int(c) for c in crcs[0, 0]]
          == [crc32c(stack[0, 0, b:b + SMALL_BLOCK_SIZE].tobytes())
              for b in range(0, stack.shape[2], SMALL_BLOCK_SIZE)],
          "batch: mesh step crcs != crc32c reference")
    step_digest = hashlib.sha256(
        parity.tobytes() + crcs.tobytes()).hexdigest()
    say(f"batch: mesh {mesh_shape}, link {link}; held mesh step "
        f"{stack.shape} on {len(placed)} devices matches the reference "
        f"({time.perf_counter() - t0:.1f}s with its compile)")

    tmp = os.path.dirname(out_path)
    master = MasterServer(volume_size_limit_mb=4 * per_vol // MIB,
                          meta_dir=tmp)
    master.start()
    servers, dirs = [], {}
    try:
        for i in range(3):
            d = os.path.join(tmp, f"vs{i}")
            os.makedirs(d)
            vs = VolumeServer(master.url(), [d])
            vs.start()
            servers.append(vs)
            dirs[vs.url()] = d

        def refresh():
            for vs in servers:
                vs._send_heartbeat(full=True)
                vs._ec_loc_cache.clear()

        # One collection per volume: the normal assign path, and every
        # volume gets the same needle sizes, so all four shard sets are
        # one shape (one compile) on the mesh.
        client = WeedClient(master.url())
        lo, hi = NEEDLE_RANGE[rehearse]
        sizes = needle_sizes(seed, 2, lo, hi, per_vol)
        vols = {}
        t0 = time.perf_counter()
        for k in range(BATCH_VOLUMES):
            coll = f"c{k}"
            rpc.call_json(
                f"{master.url()}/vol/grow?count=1&collection={coll}",
                "POST")
            needles = put_needles(client, seed, 10 + k, sizes, coll)
            vid = {wt.parse_file_id(f)[0] for f, _i, _s in needles}
            check(len(vid) == 1, f"batch: {coll} spans volumes {vid}")
            vols[vid.pop()] = (coll, 10 + k, needles)
        check(len(vols) == BATCH_VOLUMES, f"batch: volumes {list(vols)}")
        refresh()
        say(f"batch: put {BATCH_VOLUMES} x {len(sizes)} needles, "
            f"{BATCH_VOLUMES * sum(sizes) / MIB:.0f} MiB in "
            f"{time.perf_counter() - t0:.1f}s")

        env = CommandEnv(master.url())
        run_command(env, "lock")
        ids = ",".join(map(str, sorted(vols)))
        t0 = time.perf_counter()
        out = run_command(
            env, f"ec.encode -volumeId {ids} -batch "
                 f"-maxBatchMB {BATCH_ENCODE_MAX_MB}")
        t_encode = time.perf_counter() - t0
        for vid in vols:
            check(f"volume {vid} -> ec shards" in out, f"batch: {out}")
        refresh()

        def shard_paths(vid: int) -> tuple[dict, dict]:
            coll = vols[vid][0]
            locs = env.ec_shard_locations(vid)
            check(sorted(locs) == list(range(TOTAL_SHARDS)),
                  f"batch: volume {vid} shards {sorted(locs)}")
            # The batch scatter names shards without the collection
            # on holders that never held the volume.
            bases = {}
            for sid, urls in locs.items():
                for name in (f"{coll}_{vid}", str(vid)):
                    b = os.path.join(dirs[urls[0]], name)
                    if os.path.exists(b + to_ext(sid)):
                        bases[sid] = b
                check(sid in bases, f"batch: volume {vid} shard {sid} "
                                    f"not on disk at {urls[0]}")
            return ({sid: b + to_ext(sid) for sid, b in bases.items()},
                    bases)

        client = WeedClient(master.url())
        facts = {}
        for vid, (coll, stream, needles) in sorted(vols.items()):
            paths, bases = shard_paths(vid)
            facts = check_shard_set(paths, bases, bases[0] + ".ecx",
                                    seed, f"batch seal of volume {vid}")
            read_back(client, seed, stream,
                      sample(seed, stream, needles, SAMPLE_NEEDLES),
                      f"batch read through EC, volume {vid}")
        say(f"batch: ec.encode -batch of {BATCH_VOLUMES} volumes took "
            f"{t_encode:.1f}s; shards + .ecc match the reference")

        kept = {}
        for vid in vols:
            paths, _bases = shard_paths(vid)
            locs = env.ec_shard_locations(vid)
            for sid in BATCH_LOST:
                kept[vid, sid] = os.path.join(
                    tmp, f"kept_{vid}{to_ext(sid)}")
                shutil.copyfile(paths[sid], kept[vid, sid])
                rpc.call_json(
                    f"http://{locs[sid][0]}/admin/ec/delete_shards",
                    "POST", {"volume": vid, "shards": [sid]})
        refresh()
        t0 = time.perf_counter()
        out = run_command(env, "ec.rebuild -batch")
        t_rebuild = time.perf_counter() - t0
        for vid in vols:
            check(f"volume {vid}: rebuilt shards {list(BATCH_LOST)}"
                  in out, f"batch: {out}")
        refresh()
        for vid, (coll, stream, needles) in sorted(vols.items()):
            paths, bases = shard_paths(vid)
            for sid in BATCH_LOST:
                check(files_equal(paths[sid], kept[vid, sid]),
                      f"batch: volume {vid} rebuilt shard {sid} != the "
                      f"deleted bytes")
                check(ShardChecksums.load(bases[sid]).get(sid)
                      == file_block_crcs(paths[sid]),
                      f"batch: volume {vid} .ecc of rebuilt shard {sid}")
            read_back(client, seed, stream,
                      sample(seed, stream, needles, SAMPLE_NEEDLES),
                      f"batch read after rebuild, volume {vid}")
        env.close()
        say(f"batch: ec.rebuild -batch of shards {list(BATCH_LOST)} x "
            f"{BATCH_VOLUMES} volumes took {t_rebuild:.1f}s; rebuilt "
            f"files equal the deleted bytes")
    finally:
        for vs in servers:
            vs.stop()
        master.stop()

    rows = {}
    for r in roofline.LEDGER.kernel_table():
        rows[r["kernel"]] = rows.get(r["kernel"], 0) + r["bytes"]
    volume_bytes = BATCH_VOLUMES * facts["shard_bytes"] * DATA_SHARDS
    check(rows.get("batch_encode", 0)
          >= volume_bytes * TOTAL_SHARDS // DATA_SHARDS,
          f"batch: batch_encode rows {rows} do not cover the volumes")
    check(rows.get("batch_reconstruct", 0)
          >= BATCH_VOLUMES * facts["shard_bytes"]
          * (DATA_SHARDS + len(BATCH_LOST)),
          f"batch: batch_reconstruct rows {rows} do not cover the "
          f"rebuilt shards")
    check(roofline.LEDGER.conservation()["ok"],
          f"batch: {roofline.LEDGER.conservation()}")
    with open(out_path, "w") as f:
        json.dump({"pass": True, "device": device, "resolved": resolved,
                   "mesh": mesh_shape, "output_devices": len(placed),
                   "link": link, "mesh_step_sha256": step_digest,
                   "volumes": BATCH_VOLUMES, "volume_bytes": volume_bytes,
                   "encode_seconds": round(t_encode, 2),
                   "rebuild_seconds": round(t_rebuild, 2),
                   "kernel_bytes": rows, **facts}, f)
    return 0


# ---------------------------------------------------------------------------

def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU platform, Pallas in "
                         "interpret mode; never a chip result")
    ap.add_argument("--batch-child", metavar="REPORT.json",
                    help=argparse.SUPPRESS)
    ap.add_argument("--batch-bytes", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.batch_child:
        return batch_child(args.batch_child, args.seed, args.rehearse_cpu,
                           args.batch_bytes)
    sys.excepthook = _excepthook

    # A terminated launcher still stops what it started: turn SIGTERM
    # into an exit that unwinds through the legs' `finally` blocks.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.perf_counter()
    lib = native.load()
    say(f"chip_smoke: seed={args.seed} "
        f"{'REHEARSAL on the CPU platform' if args.rehearse_cpu else ''}"
        f" native library: {lib._name if lib else 'NOT BUILT'}")
    work, volume_cap = place_work_dir(args.rehearse_cpu)
    say("chip_smoke: machine " + json.dumps(MACHINE))
    try:
        served = served_leg(work, args.seed, args.rehearse_cpu, volume_cap)
        say("served leg: " + json.dumps(served))
        shutil.rmtree(os.path.join(work, "served"))
        batch = batch_leg(work, args.seed, args.rehearse_cpu, volume_cap)
        say("batch leg: " + json.dumps(batch))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    device = batch["device"]
    got = served["resolved"]
    check((got["platform"], got["device_kind"])
          == (device["platform"], device["kind"]),
          f"legs disagree on the device: {got} vs {device}")
    check("jax" not in sys.modules, "the launcher imported JAX")
    cut = ""
    batch_bytes = batch_volume_bytes(args.rehearse_cpu, volume_cap)
    if served["volumes"] > 1 or batch_bytes < BATCH_BYTES[args.rehearse_cpu]:
        cut = (f"; a file here may hold {MACHINE['file_cap'] / MIB:.0f} "
               f"MiB: {served['volumes']} served volumes for one, "
               f"{BATCH_VOLUMES} batch volumes of {batch_bytes / MIB:.0f} "
               f"MiB")
    say(f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']} "
        f"mesh={batch['mesh']} served=pass batch=pass "
        f"seal={served['seal_MBps']} MB/s "
        f"link h2d={batch['link']['h2d_MBps']} "
        f"d2h={batch['link']['d2h_MBps']} MB/s (observations, not "
        f"benchmark metrics) reduced=[30 GB volume -> "
        f"{served['volumes']} x "
        f"{served['volume_bytes'] / served['volumes'] / MIB:.0f} MiB: no "
        f"1 GB large-block row{cut}] "
        f"wall={time.perf_counter() - t0:.0f}s")
    if args.rehearse_cpu:
        say(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    check(device["platform"] == "tpu", f"not a TPU: {device}")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
