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
