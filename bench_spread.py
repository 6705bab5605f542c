"""Probe: separate K-effect from P-effect in the RS kernel column-rate
spread (BASELINE.md round 5).  Measures the fused kernel at the two
real schemes plus the two synthetic cross schemes RS(10,3)/RS(8,4):
if column rate tracks K (80 vs 64 contraction rows), the spread is
shape-structural; if it tracks P, it's output-rows-bound."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import _make_timed
from seaweedfs_tpu.ops import rs_bitmatrix
from seaweedfs_tpu.ops.coder_jax import plane_major
from seaweedfs_tpu.ops.coder_numpy import NumpyCoder
from seaweedfs_tpu.ops.coder_pallas import apply_bitmatrix_pallas

N = 64 * 1024 * 1024
BLOCK = 65536


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    dev = jax.devices()[0]
    log(f"device: {dev}")
    timed = _make_timed()
    key = jax.random.PRNGKey(0)
    out = {}
    # (k, r): real schemes + synthetic cross probes
    for k, r in ((10, 4), (8, 3), (10, 3), (8, 4), (12, 4), (14, 4)):
        total = k + r
        pm = jnp.asarray(plane_major(
            rs_bitmatrix.parity_bitmatrix(k, total, "cauchy"), r, k),
            jnp.float32)
        data = jax.random.randint(key, (k, N), 0, 256,
                                  dtype=jnp.int32).astype(jnp.uint8)
        jax.block_until_ready(data)
        want = NumpyCoder(k, r, matrix_kind="cauchy").encode(
            np.asarray(data[:, :BLOCK]))
        got = np.asarray(apply_bitmatrix_pallas(
            pm, data[:, :BLOCK], r, k, block_n=BLOCK, mm="int8"))
        assert np.array_equal(got, want), f"RS({k},{r}) wrong"
        dt = timed(apply_bitmatrix_pallas, pm, data, r, k,
                   block_n=BLOCK, mm="int8")
        mbps = data.nbytes / dt / 1e6
        cols = (N / dt) / 1e9
        pct = cols / 6.0 * 100
        log(f"RS({k:2d},{r}) int8: {mbps:8.0f} MB/s  "
            f"{cols:.2f}e9 cols/s  {pct:.0f}% of cap  (8K={8*k}, 8P={8*r})")
        out[f"rs{k}_{r}"] = {"mbps": round(mbps, 1),
                             "cols_e9": round(cols, 2),
                             "pct_cap": round(pct, 1)}
        del data
    print(json.dumps(out))


if __name__ == "__main__":
    main()
