"""ErasureCoder backend selection.

The reference hides klauspost/reedsolomon behind direct calls in
`ec_encoder.go`; BASELINE.json's design point is an `ErasureCoder`
interface seam that picks a backend at startup.  Backends:

- "numpy":  table-lookup oracle (always available, slow)
- "native": C++ AVX2 PSHUFB kernels (klauspost-class CPU path; built
            from native/ on first use, utils/native.py)
- "jax":    XLA bit-sliced matmul (any jax backend)
- "pallas": fused MXU kernel (TPU; interpreter mode elsewhere)

Selection: SEAWEEDFS_TPU_CODER env var, else pallas on TPU, else native
if built, else jax (`default_backend`; no fallback when the device
cannot be reached).  That variable and SEAWEEDFS_TPU_EC_FUSED_CRC
(ops/crc_fold.py) are the hot layer's only hand-set switches: they are
how the benchmark's CPU rehearsal, `chip_smoke.py --rehearse-cpu` and
the tests run the device path where there is no chip, and how the
fused CRC will be measured against the CPU pass (ROADMAP A8).
All backends share the same API: encode / encode_all / reconstruct / verify,
operating on (shards, n) uint8 arrays; results are byte-identical.
"""

from __future__ import annotations

import os
from typing import Protocol

import numpy as np


class ErasureCoder(Protocol):
    data_shards: int
    parity_shards: int
    total_shards: int

    def encode(self, data) -> np.ndarray: ...
    def encode_all(self, data) -> np.ndarray: ...
    def reconstruct(self, shards: dict[int, np.ndarray],
                    wanted: list[int] | None = None) -> dict[int, np.ndarray]: ...
    def verify(self, shards) -> bool: ...


_BACKENDS = ("numpy", "native", "jax", "pallas")

# Widths (bytes a row) of the reconstruct calls a READ makes: a degraded
# GET, the scrub's block repair, `reconstruct`.  The survivors of an
# interval are padded on the host to the smallest of these that holds
# them, and the wanted rows to the coder's `read_rows`, so that a width
# has ONE program whatever the interval's size and the loss pattern:
# nothing is compiled per interval.  An interval of the served path
# never exceeds one small block (1 MiB); a wider call goes in pieces of
# the last width.  They live here, off JAX, because the server's read
# path (ec/degraded.py) sizes its host buffers by them with any coder.
#
# Steps of four, so a padded call moves at most four times its
# interval's bytes.  The chip's readings (PR 36, `_stage/a8_widths.py`
# on a v5e, the rung's own statements on a pooled (10, W) buffer, ms a
# call at the median, one wanted row / four; PERF.md section 6 has the
# table).  ONE caller, device: 4 KiB 1.32 / 1.35, 16 KiB 1.40 / 1.47,
# 64 KiB 1.63 / 1.66, 256 KiB 2.75 / 2.85, 1 MiB 6.29 / 6.77 — flat to
# 64 KiB (the transfers' and the launch's fixed cost), then the bytes'.
# `NativeCoder.reconstruct` beside it: 0.29 / 0.30, 0.38 / 0.33, 0.36 /
# 0.44, 0.49 / 0.82, 1.16 / 2.47: the host coder wins three- to fivefold
# at every width.  SIXTEEN callers at once, as the upstream shape has
# them, device: 7.8 / 8.3, 8.0 / 8.3, 8.4 / 8.7, 9.6 / 11.3, 19.2 / 22.2;
# host coder: 36.6 / 38.7, 36.8 / 38.5, 38.2 / 39.6, 42.0 / 43.3, 46.4 /
# 53.9 — its call drops and retakes the interpreter's lock a few times
# and each retake waits its turn, so beside other threads it loses
# two- to fivefold at every width.  The rung therefore has one path,
# the coder the process resolved (ROADMAP A8, for this path).
READ_WIDTHS = (4 << 10, 16 << 10, 64 << 10, 256 << 10, 1 << 20)


def read_width(n: int) -> int:
    """The smallest of READ_WIDTHS that holds `n` bytes, the last for
    a wider `n` (the caller then goes in pieces)."""
    for w in READ_WIDTHS:
        if n <= w:
            return w
    return READ_WIDTHS[-1]


def _native_available() -> bool:
    from ..utils import native as native_mod
    return native_mod.load() is not None


def default_backend() -> str:
    """The coder this process uses: the SEAWEEDFS_TPU_CODER override,
    else pallas when JAX resolves to a TPU, else native if built, else
    jax.  Asking initialises the JAX backend (on a TPU host that claims
    the chip — utils/jaxenv.py has the cluster -> chip map) and a
    backend that cannot initialise raises: a process meant to own a
    chip must not quietly code on the CPU instead."""
    env = os.environ.get("SEAWEEDFS_TPU_CODER")
    if env:
        if env not in _BACKENDS:
            raise ValueError(
                f"SEAWEEDFS_TPU_CODER={env!r}; expected one of {_BACKENDS}")
        return env
    from ..utils import jaxenv
    if jaxenv.platform() == "tpu":
        return "pallas"
    return "native" if _native_available() else "jax"


def describe_backend() -> str:
    """One start-up log line's worth: the coder backend and the devices
    JAX resolved behind it.  Resolves (and so claims) the device like
    the first EC call would."""
    backend = default_backend()
    if backend in ("numpy", "native") \
            and os.environ.get("SEAWEEDFS_TPU_CODER"):
        return f"coder={backend} (SEAWEEDFS_TPU_CODER; no JAX device)"
    from ..utils import jaxenv
    d = jaxenv.device_summary()
    return (f"coder={backend} platform={d['platform']} "
            f"device_kind={d['device_kind']!r} devices={d['count']}")


def new_coder(data_shards: int = 10, parity_shards: int = 4,
              matrix_kind: str = "vandermonde",
              backend: str | None = None, codec=None) -> ErasureCoder:
    """Build a coder.  `codec` (a registered codec name or Codec
    object, e.g. "lrc") overrides the RS shard-count arguments — the
    codec IS the scheme; the backend is just where the matmul runs."""
    backend = backend or default_backend()
    if backend == "numpy":
        from .coder_numpy import NumpyCoder
        return NumpyCoder(data_shards, parity_shards, matrix_kind, codec)
    if backend == "native":
        from .coder_native import NativeCoder
        return NativeCoder(data_shards, parity_shards, matrix_kind, codec)
    if backend == "jax":
        from .coder_jax import JaxCoder
        return JaxCoder(data_shards, parity_shards, matrix_kind, codec)
    if backend == "pallas":
        from .coder_pallas import PallasCoder
        return PallasCoder(data_shards, parity_shards, matrix_kind,
                           codec=codec)
    raise ValueError(f"unknown erasure backend {backend!r}")
