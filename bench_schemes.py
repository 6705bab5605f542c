"""Per-scheme kernel throughput: RS(10,4) / RS(16,4) / RS(8,3) /
LRC(10,2,2), int8+bf16.

Produces the measurement table in BASELINE.md's "Kernel roofline
analysis" (execution-fenced via bench.py's shared harness).  The column
rate it prints is the model quantity: throughput = k bytes/column x
column rate, column rate <= 6.0e9/s on v5e whatever fraction of the
128x128 MXU weight tile the (8r, 8k) bit-matrix fills.  The LRC row
runs the SAME kernel with the lrc codec's generator — encode cost is
identical by construction (same (8*4, 8*10) matrix shape as RS(10,4));
what LRC buys is 2x cheaper repair (bench_repair_traffic.py).

Run on a real chip: python bench_schemes.py

`python bench_schemes.py --roofline [out.json]` runs the device
roofline pass instead: small-N end-to-end PallasCoder encodes per
(codec, mm dtype) through the REAL call sites (so the achieved
fractions, conservation verdict, and armed-vs-disarmed overhead all
come from stats/roofline.py's production ledger, not a parallel
harness), published as BENCH_roofline_r01.json.  Small-N on purpose:
it completes in interpret mode on a CPU-only box; on a real chip the
same command gives honest achieved fractions against the probed peaks.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import _make_timed, roofline_limit_mbps
from seaweedfs_tpu.codecs import get_codec, rs_codec
from seaweedfs_tpu.ops.coder_jax import plane_major
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.ops.coder_pallas import apply_bitmatrix_pallas
from seaweedfs_tpu.stats import roofline as rl

N = 64 * 1024 * 1024
BLOCK = 65536


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    dev = jax.devices()[0]
    log(f"device: {dev}")
    timed = _make_timed()
    key = jax.random.PRNGKey(0)
    results = {}
    schemes = [
        ("RS(10,4)", "rs10_4", rs_codec(10, 4)),
        ("RS(16,4)", "rs16_4", rs_codec(16, 4)),
        ("RS( 8,3)", "rs8_3", rs_codec(8, 3)),
        ("LRC(10,2,2)", "lrc10_2_2", get_codec("lrc")),
    ]
    for label, keybase, cd in schemes:
        k, r = cd.data_shards, cd.parity_shards
        pm = jnp.asarray(plane_major(
            cd.parity_bitmatrix(), r, k), jnp.float32)
        # GF(2) work columns: naive XOR count beside the
        # post-elimination schedule (Paar greedy) — the baseline pair
        # matrix-scheduling work (arxiv 2108.02692) lands against.
        bm = np.asarray(cd.parity_bitmatrix())
        dense = rl.dense_gf2_work(bm)
        eff = rl.effective_gf2_work(bm)
        log(f"{label:>11s} GF(2) work: dense {dense} XORs, "
            f"effective {eff} ({eff / dense:.0%} after elimination)")
        results[f"{keybase}_gf2_dense_xors"] = dense
        results[f"{keybase}_gf2_effective_xors"] = eff
        data = jax.random.randint(key, (k, N), 0, 256,
                                  dtype=jnp.int32).astype(jnp.uint8)
        jax.block_until_ready(data)
        want = NumpyCoder(codec=cd).encode(np.asarray(data[:, :BLOCK]))
        limit = roofline_limit_mbps(r, k)
        peaks = rl.probe_peaks()
        for mm in ("int8", "bf16"):
            # correctness gate per scheme AND dtype: an untested
            # lowering must never publish a number.
            got = np.asarray(apply_bitmatrix_pallas(
                pm, data[:, :BLOCK], r, k, block_n=BLOCK, mm=mm))
            assert np.array_equal(got, want), f"{label} {mm} wrong"
            dt = timed(apply_bitmatrix_pallas, pm, data, r, k,
                       block_n=BLOCK, mm=mm)
            mbps = data.nbytes / dt / 1e6
            if dev.platform == "tpu" and mbps > 1.05 * limit:
                log(f"{label} {mm}: REJECT {mbps:.0f} MB/s — "
                    f"exceeds the physical roofline {limit:.0f} MB/s "
                    f"(harness bug, not a result)")
                continue
            cols = (N / dt) / 1e9
            # Achieved fraction of the MEASURED roofline (probed
            # matmul peak / membw), beside the analytic MB/s limit —
            # the same floor the production ledger applies.
            cost = rl.cost_model(r, k, N)
            floor = rl.roofline_floor_seconds(
                cost["flops"], cost["bytes"], peaks, mm)
            ach = None if floor is None else min(floor / dt, 1.0)
            log(f"{label:>11s} {mm}: {mbps:8.0f} MB/s "
                f"({cols:.2f}e9 cols/s, {k}B/col"
                + (f", {ach:.1%} of probed roofline" if ach is not None
                   else "") + ")")
            results[f"{keybase}_{mm}"] = round(mbps, 1)
            if ach is not None:
                results[f"{keybase}_{mm}_achieved"] = round(ach, 4)
        del data
    print(json.dumps(results))


def bench_roofline(out: str = "BENCH_roofline_r01.json") -> None:
    """Per-kernel achieved-fraction rows for rs(10,4) and lrc(10,2,2)
    x int8/bf16 through the production ledger: real PallasCoder
    encodes (plain + fused-CRC) fill stats/roofline.LEDGER, whose
    kernel table, conservation verdict, and peaks are what this
    publishes — plus the armed-vs-disarmed overhead of the plane
    itself."""
    # One `.ecc` block: the narrowest width the fused-CRC kernel takes.
    n = int(os.environ.get("BENCH_ROOFLINE_N", str(1024 * 1024)))
    reps = int(os.environ.get("BENCH_ROOFLINE_REPS", "3"))
    dev = jax.devices()[0]
    log(f"device: {dev}  n={n} bytes/shard  reps={reps}")
    rl.LEDGER.reset()
    rl.set_armed(True)
    peaks = rl.probe_peaks()
    key = jax.random.PRNGKey(0)

    from seaweedfs_tpu.ops.coder_pallas import PallasCoder
    gf2 = {}
    coders = []
    for codec_name in ("rs", "lrc"):
        for mm in ("int8", "bf16"):
            coders.append((codec_name, mm,
                           PallasCoder(codec=codec_name, mm=mm)))
    for codec_name, mm, pc in coders:
        bm = np.asarray(pc.codec.parity_bitmatrix())
        gf2[pc.codec.name] = {
            "dense_xors": rl.dense_gf2_work(bm),
            "effective_xors": rl.effective_gf2_work(bm)}
        k = pc.data_shards
        data = jax.random.randint(key, (k, n), 0, 256,
                                  dtype=jnp.int32).astype(jnp.uint8)
        jax.block_until_ready(data)
        for _ in range(reps):
            pc.encode(data)          # records encode_kernel
        if pc.fused_crc_ok:
            for _ in range(reps):
                pc.encode_with_crc(data)   # records encode_crc_kernel
        log(f"{pc.codec.name} {mm}: {2 * reps} fenced encodes recorded")

    # Plane overhead: the same encode with the ledger disarmed — the
    # difference is what always-on roofline accounting costs; the
    # disarmed path itself is one flag check (tests assert that).
    codec_name, mm, pc = coders[0]
    data = jax.random.randint(key, (pc.data_shards, n), 0, 256,
                              dtype=jnp.int32).astype(jnp.uint8)
    jax.block_until_ready(data)

    def wall(reps_=5):
        t0 = time.perf_counter()
        for _ in range(reps_):
            jax.block_until_ready(pc.encode(data))
        return (time.perf_counter() - t0) / reps_

    wall(2)  # warm
    armed_s = wall()
    rl.set_armed(False)
    disarmed_s = wall()
    rl.set_armed(True)
    overhead = {"armed_seconds_per_encode": round(armed_s, 6),
                "disarmed_seconds_per_encode": round(disarmed_s, 6),
                "overhead_fraction": round(
                    max(armed_s - disarmed_s, 0.0)
                    / max(disarmed_s, 1e-12), 6)}
    log(f"plane overhead: armed {armed_s * 1e3:.2f}ms vs disarmed "
        f"{disarmed_s * 1e3:.2f}ms per encode "
        f"({overhead['overhead_fraction']:.2%})")

    cons = rl.LEDGER.conservation()
    assert cons["ok"], f"conservation violated: {cons['violations']}"
    doc = {"round": 1, "platform": dev.platform, "n_bytes": n,
           "reps": reps, "peaks": peaks,
           "kernels": rl.LEDGER.kernel_table(),
           "gf2_work": gf2, "conservation": cons,
           "overhead": overhead}
    with open(out, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    log(f"wrote {out} ({len(doc['kernels'])} kernel rows, "
        f"conservation {'OK' if cons['ok'] else 'VIOLATED'})")


if __name__ == "__main__":
    if "--roofline" in sys.argv:
        args = [a for a in sys.argv[1:] if not a.startswith("--")]
        bench_roofline(*args[:1])
    else:
        main()
