#!/usr/bin/env python3
"""BASELINE config #3: 256-volume batched EC rebuild wall-clock.

Measures the mesh-batched decode machinery (`batched_reconstruct`
grouped exactly as `ec.rebuild -batch` groups volumes) over 256
synthetic volumes that all lost the same 3 shards — the compiled-step
pipeline without the HTTP gather/scatter, which on this 1-core box
would measure the loopback stack, not the codec.

Runs on whatever JAX resolves — `JAX_PLATFORMS` decides, as for every
other entry point — over every device of that platform, and names the
platform, device kind and count in its output.  For the virtual CPU
mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python bench_batch_rebuild.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

VOLUMES = int(os.environ.get("BENCH_REBUILD_VOLUMES", "256"))
SHARD_BYTES = int(os.environ.get("BENCH_REBUILD_SHARD_BYTES",
                                 str(1024 * 1024)))
LOST = (2, 7, 11)  # 3 shards lost (BASELINE config #3)
MAX_BATCH = 1 << 28


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    from seaweedfs_tpu.utils.jaxenv import place_compile_cache
    place_compile_cache()
    import jax

    from seaweedfs_tpu.parallel.cluster_rebuild import make_mesh
    from seaweedfs_tpu.parallel.sharded_codec import batched_reconstruct

    mesh = make_mesh()
    devs = jax.devices()
    where = (f"platform={devs[0].platform} device_kind="
             f"{devs[0].device_kind!r} devices={len(devs)}")
    log(f"mesh: {dict(mesh.shape)} over {where}")
    present = tuple(s for s in range(14) if s not in LOST)
    used = present[:10]

    rng = np.random.default_rng(0)
    per_vol = SHARD_BYTES * (10 + len(LOST))
    vol_axis = mesh.shape["vol"]
    chunk_v = max(1, min(VOLUMES, MAX_BATCH // per_vol))
    chunk_v = max(vol_axis, chunk_v - chunk_v % vol_axis)
    log(f"{VOLUMES} volumes x {SHARD_BYTES >> 10}KB shards, "
        f"{chunk_v} volumes/step")

    # One representative stacked batch, reused for every step — the
    # gather is not what's being measured, and jit dispatch does not
    # cache across identical calls (each step executes fully; the
    # fenced block_until_ready proves it).
    stacked = rng.integers(0, 256, (chunk_v, 10, SHARD_BYTES),
                           dtype=np.uint8)

    # Warm: compile the step once.
    out = batched_reconstruct(stacked, present, LOST, mesh)
    jax.block_until_ready(out)

    # Every step runs a full chunk (the production path pads the tail
    # batch to the vol axis the same way).
    steps = -(-VOLUMES // chunk_v)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = batched_reconstruct(stacked, present, LOST, mesh)
        jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    data_bytes = VOLUMES * 10 * SHARD_BYTES
    print(json.dumps({
        "metric": f"batched ec.rebuild decode wall-clock on "
                  f"{devs[0].platform}, {VOLUMES} volumes x "
                  f"{SHARD_BYTES >> 10}KB shards, 3 lost",
        "value": round(dt, 2),
        "unit": "s",
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "vs_baseline": None,
        "note": f"{steps} compiled steps on a "
                f"{dict(mesh.shape)} mesh ({where}); "
                f"{data_bytes / dt / 1e6:.0f} MB/s of volume data; "
                f"decode only — HTTP gather/scatter excluded",
    }))


if __name__ == "__main__":
    main()
