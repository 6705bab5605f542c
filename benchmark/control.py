#!/usr/bin/env python3
"""The control of a cell's comparison: a run that has to come out as NOT
correct.

    python3 benchmark/control.py --workload <name> --seed <n> --seconds <s>

The system states no precision, so the control breaks one guarantee that
the configuration states, with the reference put in the program's place:

  jobs on erasure-coded volumes   once the window has closed, one parity
      shard of the last volume is written again by the reference with
      the guarantee broken — the plain XOR of the data shards (a RAID-5
      row) where a Reed-Solomon row belongs: the cheaper code that would
      tempt a later PR — and `.ecc` follows it, so only the comparison
      of parity with the reference can tell.
  requests   one acknowledged write in 16 is torn underneath the server
      (256 bytes of its payload zeroed in the volume file), as a store
      that acknowledges before its bytes are safe leaves them: before the
      window for a read mix, after it for a write mix.
  reads of the pool's needles (`keys_from: "pool"`)   before the window,
      in the pool's last volume, one surviving shard that the decode of
      the first lost data shard reads (the last of the first ten
      survivors: a parity shard, so that no healthy read passes over it)
      is written anew, never through the hard link, with 256 bytes
      zeroed every 4 KiB — the smallest needle, so that in each 1 MiB
      block every lost interval of a needle's length decodes from some —
      and `.ecc` left as it was; the volume is taken off the server and
      mounted again through its own admin routes, since the server holds
      the old file open.  Any 10 shards no longer give back every needle:
      whichever of the server's checks meets it, a read fails or differs.

Exit code 0 where `correct` came out false, 1 where the comparison let
the control through.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import glob
import json
import mmap
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import ecref, loadgen, run  # noqa: E402
from benchmark.data import payload_block, request_payload  # noqa: E402
from benchmark.machine import MIB, check  # noqa: E402
from benchmark.served import call  # noqa: E402

TORN_ONE_IN = 16
TORN_BYTES = 256
TORN_EVERY = 4096


def xor_parity_shard(base: str, sid: int) -> None:
    """Shard `sid` of a sealed volume as the broken reference writes it:
    the XOR of the ten data shards, with its `.ecc` entries to match."""
    size = os.path.getsize(base + ecref.ext(0))
    path = base + ecref.ext(sid)
    os.unlink(path)                  # never through a hard link
    crcs = []
    ins = [open(base + ecref.ext(d), "rb")
           for d in range(ecref.DATA_SHARDS)]
    try:
        with open(path, "wb") as out:
            for _ in range(0, size, ecref.BLOCK):
                acc = np.zeros(ecref.BLOCK, np.uint8)
                for f in ins:
                    acc ^= np.frombuffer(f.read(ecref.BLOCK), np.uint8)
                out.write(acc.tobytes())
                crcs.append(ecref.crc32c(acc.tobytes()))
    finally:
        for f in ins:
            f.close()
    with open(base + ".ecc") as f:
        doc = json.load(f)
    doc["shards"][str(sid)] = [f"{c:08x}" for c in crcs]
    with open(base + ".ecc", "w") as f:
        json.dump(doc, f)


def tear_shard(base: str, sid: int) -> None:
    """Shard `sid` of a sealed volume as a disk that lost part of every
    sector run leaves it: a new file (never through the hard link) with
    TORN_BYTES zeroed every TORN_EVERY bytes; `.ecc` is not touched."""
    path = base + ecref.ext(sid)
    with open(path, "rb") as f:
        raw = bytearray(f.read())
    check(raw and len(raw) % TORN_EVERY == 0,
          f"{path}: {len(raw)} bytes are no whole blocks")
    np.frombuffer(raw, np.uint8).reshape(-1, TORN_EVERY)[
        :, 16:16 + TORN_BYTES] = 0
    os.unlink(path)
    with open(path, "wb") as f:
        f.write(raw)


def tear_writes(data_dir: str, block: bytes, idents, size: int,
                seed: int) -> int:
    """Zero part of the payload of one in TORN_ONE_IN of `idents` in
    the volume files; how many were torn."""
    idents = np.asarray(idents)
    pick = np.random.default_rng([seed, 80]).choice(
        idents, max(1, len(idents) // TORN_ONE_IN), replace=False)
    torn = 0
    maps = []
    for path in glob.glob(os.path.join(data_dir, "bench_*.dat")):
        f = open(path, "r+b")
        maps.append((f, mmap.mmap(f.fileno(), 0)))
    try:
        for ident in pick:
            head = request_payload(block, int(ident), size)[:32]
            for _f, mm in maps:
                at = mm.find(head)
                if at >= 0:
                    mm[at + 16:at + 16 + TORN_BYTES] = bytes(TORN_BYTES)
                    torn += 1
                    break
    finally:
        for f, mm in maps:
            mm.flush()
            mm.close()
            f.close()
    return torn


class ControlHooks(run.Hooks):
    def before_window(self, ctx: dict) -> None:
        req = ctx.get("requests")
        if ctx["pool_keys"]:
            srv, tpl = ctx["srv"], ctx["template"]
            lost = run.pool_lost_shards(ctx)
            check(any(s < ecref.DATA_SHARDS for s in lost),
                  f"no data shard is lost ({lost}): no read decodes")
            sid = [s for s in range(ecref.TOTAL_SHARDS)
                   if s not in lost][ecref.DATA_SHARDS - 1]
            vid = ctx["vids"][-1]
            call(f"{srv.volume}/admin/ec/unmount", {"volume": vid})
            tear_shard(os.path.join(srv.data_dir,
                                    f"{tpl.collection}_{vid}"), sid)
            call(f"{srv.volume}/admin/ec/mount", {"volume": vid})
            print(f"control: shard {sid} of volume {vid} written anew "
                  f"with {TORN_BYTES} bytes in every {TORN_EVERY} zeroed",
                  flush=True)
        elif req and req["op"] == "read":
            with np.load(ctx["gen"]["keys"]) as z:
                idents = z["ids"]
            torn = tear_writes(ctx["srv"].data_dir,
                               payload_block(ctx["seed"]), idents,
                               ctx["store"]["size"], ctx["seed"])
            check(torn, "the control tore no write")
            print(f"control: tore {torn} of {len(idents)} keys",
                  flush=True)

    def after_window(self, ctx: dict) -> None:
        req = ctx.get("requests")
        if req and req["op"] == "write":
            res, inside = ctx["res"], ctx["req"]["inside"]
            idents = res["ids"][inside & (res["ok"] == loadgen.GOOD)]
            torn = tear_writes(ctx["srv"].data_dir,
                               payload_block(ctx["seed"]), idents,
                               ctx["store"]["size"], ctx["seed"])
            check(torn, "the control tore no write")
            print(f"control: tore {torn} of {len(idents)} writes",
                  flush=True)
        elif not req:
            tpl, lost = ctx["template"], ctx["ec"]["lost_shards"]
            vid = ctx["job"]["jobs"][-1][0]     # the one always compared
            base = os.path.join(ctx["srv"].data_dir,
                                f"{tpl.collection}_{vid}")
            parity = [s for s in lost if s >= ecref.DATA_SHARDS]
            sid = parity[0] if ctx["jobs"]["op"] == "ec.rebuild" \
                else ecref.TOTAL_SHARDS - 1
            xor_parity_shard(base, sid)
            mib = os.path.getsize(base + ecref.ext(sid)) // MIB
            print(f"control: shard {sid} of volume {vid} rewritten as "
                  f"the XOR row ({mib} MiB)", flush=True)


def main(argv: list[str]) -> int:
    try:
        result = run.run(run.parse(argv), ControlHooks())
    except run.BenchFailure as e:
        print(f"control: FAILED: {e}", file=sys.stderr, flush=True)
        return 2
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
