#!/usr/bin/env python3
"""EC RS(10,4) throughput benchmark — prints ONE JSON line to stdout.

Metric: MB/s of volume data through an encode+reconstruct round trip on one
chip (the BASELINE.json north-star metric).  vs_baseline is the ratio to
the same round trip on the CPU via the native AVX2 PSHUFB coder
(klauspost-class, the reference's CPU path).

Design notes:
- Benchmark data is generated ON DEVICE: this is the kernel-only layer
  metric, and a host->device copy inside the loop would measure the
  link instead.
- The timed loop is EXECUTION-FENCED: each iteration's output is folded
  into an on-device scalar accumulator, and the accumulator is
  host-fetched inside the timed region — a host fetch of a value that
  transitively depends on every iteration cannot return early.
- A roofline guard rejects any measurement that implies more FLOPs or
  HBM bytes than the chip can physically deliver — a too-good number is
  a harness bug, not a result.
- The metric is a chip metric.  Without a TPU the script exits non-zero
  and prints no result: a CPU timing is never reported under its name.

All diagnostics go to stderr; stdout carries exactly one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SHARD_MB = int(os.environ.get("BENCH_SHARD_MB", "64"))
N = SHARD_MB * 1024 * 1024  # bytes per shard per call
ITERS = int(os.environ.get("BENCH_ITERS", "10"))
LOST = (2, 7, 11, 13)  # worst case: 4 shards lost

# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.
# Used to REJECT impossible measurements (a reading over the roofline
# is a harness bug).  The kernel does a (8*out_rows, 8*in_rows) @
# (8*in_rows, n) matmul per n bytes/shard: 512 flops and 1.4 HBM bytes
# per data byte for RS(10,4) encode.  A device that is not in the table
# is an error, not a default.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM.
    "TPU v5 lite": {"flops": 197e12, "hbm_bps": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py: no published peaks for device_kind "
            f"{device_kind!r}; add it to PEAKS with its source") from None


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def roofline_limit_mbps(peaks: dict, out_rows: int = 4,
                        in_rows: int = 10) -> float:
    """Max physically possible data-MB/s for the bitmatrix kernel —
    the REJECT threshold (a measurement above this is a harness bug)."""
    flops_per_byte = 2.0 * (8 * out_rows) * (8 * in_rows) / in_rows
    hbm_per_byte = (in_rows + out_rows) / in_rows
    return min(peaks["flops"] / flops_per_byte,
               peaks["hbm_bps"] / hbm_per_byte) / 1e6


def shape_ceiling_mbps(peaks: dict, in_rows: int = 10) -> float:
    """The ATTAINABLE ceiling for an (8r, 8k) matrix: the MXU streams
    one K-vector (= one byte-column = k data bytes) per column-slot at
    peak/(2*128*128) columns/s whatever fraction of the 128x128 weight
    tile the matrix fills — padding is structurally forfeit flops.  See
    BASELINE.md 'Kernel roofline analysis'."""
    cols_per_sec = peaks["flops"] / (2.0 * 128 * 128)
    return in_rows * cols_per_sec / 1e6


def bench_cpu() -> tuple[float, str]:
    """CPU round-trip MB/s + the coder actually used (single thread)."""
    from seaweedfs_tpu.ops.erasure import new_coder
    try:
        coder = new_coder(backend="native")
    except Exception as e:  # noqa: BLE001
        log(f"native coder unavailable ({e}); numpy fallback baseline")
        coder = new_coder(backend="numpy")
    n = min(N, 4 * 1024 * 1024)  # CPU pass is slow; 40MB per iter is ample
    data = np.random.default_rng(0).integers(
        0, 256, (10, n)).astype(np.uint8)
    shards = coder.encode_all(data)
    present = [i for i in range(14) if i not in LOST]
    have = {i: shards[i] for i in present}
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        coder.encode(data)
        coder.reconstruct(have, wanted=list(LOST))
    dt = (time.perf_counter() - t0) / iters
    mbps = data.nbytes / dt / 1e6
    name = type(coder).__name__
    log(f"cpu round-trip: {mbps:.0f} MB/s ({name})")
    return mbps, name


def _make_timed():
    """Build an execution-fenced timer (see module docstring)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _chain(acc, out):
        # Folding any slice of `out` into the accumulator makes the
        # final host fetch wait on the whole kernel that produced it
        # (kernels complete atomically); the slice keeps the fence's
        # own HBM traffic negligible.
        return acc ^ out[:, :256].astype(jnp.uint32).sum()

    def timed(fn, *args, iters=ITERS, **kw):
        out = fn(*args, **kw)
        acc = _chain(jnp.uint32(0), out)
        int(acc)  # warm: compile both, drain the pipe
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args, **kw)
            acc = _chain(acc, out)
        sink = int(acc)  # host fetch INSIDE the timed region: the fence
        dt = (time.perf_counter() - t0) / iters
        del sink
        return dt

    return timed


def bench_tpu(dev) -> dict | None:
    import jax
    import jax.numpy as jnp

    peaks = peaks_for(dev.device_kind)

    from seaweedfs_tpu.ops import rs_bitmatrix
    from seaweedfs_tpu.ops.coder_jax import plane_major
    from seaweedfs_tpu.ops.coder_pallas import (LANES, WORD_ROW,
                                                apply_bitmatrix_pallas,
                                                tile_matrix)
    from seaweedfs_tpu.ops.erasure import host_rows

    enc_mat = jnp.asarray(tile_matrix(plane_major(
        rs_bitmatrix.parity_bitmatrix(10, 14), 4, 10)))
    present = tuple(i for i in range(14) if i not in LOST)
    dec_b, _used = rs_bitmatrix.decode_bitmatrix(10, 14, present, LOST)
    dec_mat = jnp.asarray(tile_matrix(plane_major(np.asarray(dec_b), 4, 10)))

    # On-device data in the kernels' transfer layout: (10, N / 512,
    # 128) uint32 words, the row-major bytes of a (10, N) chunk.
    key = jax.random.PRNGKey(0)
    data = jax.random.bits(key, (10, N // WORD_ROW, LANES), jnp.uint32)
    jax.block_until_ready(data)
    timed = _make_timed()
    limit = roofline_limit_mbps(peaks)

    def checked_mbps(dt: float, what: str) -> float | None:
        mbps = data.nbytes / dt / 1e6
        if mbps > 1.05 * limit:
            log(f"  REJECT {what}: {mbps:.0f} MB/s exceeds the physical "
                f"roofline ({limit:.0f} MB/s) — harness bug, not a result")
            return None
        return mbps

    # Self-tune the kernel.
    best = None
    for block_n in (8192, 16384, 32768, 65536):
        for mm in ("bf16", "int8"):
            try:
                dt = timed(apply_bitmatrix_pallas, enc_mat, data, 4, 10,
                           block_n=block_n, mm=mm, iters=3)
                mbps = checked_mbps(dt, f"tune {block_n}/{mm}")
                if mbps is None:
                    continue
                log(f"  tune block_n={block_n:6d} mm={mm}: {mbps:8.0f} MB/s")
                if best is None or mbps > best[0]:
                    best = (mbps, block_n, mm)
            except Exception as e:  # noqa: BLE001
                log(f"  tune block_n={block_n} mm={mm}: FAIL "
                    f"{type(e).__name__}: {str(e)[:80]}")
    if best is None:
        return None
    _, block_n, mm = best
    log(f"selected block_n={block_n} mm={mm} "
        f"(roofline {limit:.0f} MB/s)")

    t_enc = timed(apply_bitmatrix_pallas, enc_mat, data, 4, 10,
                  block_n=block_n, mm=mm)
    # Reconstruction: same kernel, decode matrix over the 10 survivors.
    t_dec = timed(apply_bitmatrix_pallas, dec_mat, data, 4, 10,
                  block_n=block_n, mm=mm)
    enc_mbps = checked_mbps(t_enc, "encode")
    dec_mbps = checked_mbps(t_dec, "reconstruct")
    if enc_mbps is None or dec_mbps is None:
        return None
    rt_mbps = data.nbytes / (t_enc + t_dec) / 1e6
    # Correctness spot check against the oracle on a slice.
    from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
    sl = np.asarray(data[:, :65536 // WORD_ROW])
    got = host_rows(apply_bitmatrix_pallas(
        enc_mat, jnp.asarray(sl), 4, 10, block_n=block_n, mm=mm))
    ok = np.array_equal(got, NumpyCoder(10, 4).encode(host_rows(sl)))
    log(f"encode {enc_mbps:.0f} MB/s, reconstruct {dec_mbps:.0f} MB/s, "
        f"round-trip {rt_mbps:.0f} MB/s, correct={ok}")
    if not ok:
        return None
    return {"enc": enc_mbps, "dec": dec_mbps, "rt": rt_mbps,
            "block_n": block_n, "mm": mm,
            "roofline_mbps": limit,
            "shape_ceiling_mbps": shape_ceiling_mbps(peaks)}


def main() -> int:
    from seaweedfs_tpu.utils import jaxenv
    jaxenv.place_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: {dev} platform={dev.platform} "
        f"kind={dev.device_kind!r} count={len(devs)}")
    if dev.platform != "tpu":
        log("bench.py measures the chip and JAX resolved no TPU; "
            "reporting nothing (a CPU timing is never written under a "
            "chip metric's name)")
        return 1

    cpu_mbps, cpu_coder = bench_cpu()
    cpu_desc = ("cpu native avx2" if cpu_coder == "NativeCoder"
                else f"cpu {cpu_coder} (native lib NOT built)")
    res = bench_tpu(dev)
    if res is None:
        log("no admissible device measurement (see REJECT / correctness "
            "lines above)")
        return 1
    ceiling = res["shape_ceiling_mbps"]
    print(json.dumps({
        "metric": "EC RS(10,4) encode+reconstruct(4 lost) MB/s per chip",
        "value": round(res["rt"], 1),
        "unit": "MB/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(devs),
        "vs_baseline": round(res["rt"] / cpu_mbps, 3) if cpu_mbps else None,
        "note": (f"pallas mxu kernel, block_n={res['block_n']} "
                 f"mm={res['mm']}; encode {res['enc']:.0f} MB/s "
                 f"({100 * res['enc'] / ceiling:.0f}% of the "
                 f"{ceiling / 1e3:.0f} GB/s shape ceiling - see "
                 f"BASELINE.md roofline analysis), reconstruct "
                 f"{res['dec']:.0f} MB/s; execution-fenced; {cpu_desc} "
                 f"baseline {cpu_mbps:.0f} MB/s"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
