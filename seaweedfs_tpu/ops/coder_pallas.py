"""Fused Pallas TPU kernel for Reed-Solomon GF(2^8) coding on the MXU.

The XLA path (`coder_jax.py`) materializes the unpacked bit planes (an 8x
expansion of the data) in HBM between the unpack and the matmul.  This
kernel fuses the whole pipeline per tile in VMEM:

    HBM --(k,BN) bytes--> VMEM
        unpack to (8k,BN) bit planes            (VPU shifts)
        (8r,8k) @ (8k,BN) bf16 matmul, f32 acc  (MXU)
        mod-2 + pack to (r,BN) bytes            (VPU)
    VMEM --(r,BN) bytes--> HBM

so HBM traffic stays at bytes-in + bytes-out while the GF math runs at MXU
rate.  This is the TPU replacement for klauspost/reedsolomon's AVX2 galois
kernels (reference hot loop: weed/storage/erasure_coding/ec_encoder.go:162,
store_ec.go:322).

The same kernel serves encode (B = parity bit-matrix) and reconstruction
(B = decode bit-matrix for the survivor set) — only the matrix changes.
"""

from __future__ import annotations

import collections
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stats import roofline as _roofline
from ..stats.metrics import observe_ec_stage
from ..utils import jaxenv
from . import crc_fold
from .erasure import READ_WIDTHS, read_width


def _record_roofline(kernel: str, coder, *, out_rows: int,
                     in_rows: int, n: int, crc: bool,
                     seconds: float, measured_bytes: int) -> None:
    """Feed one execution-fenced kernel wall into the roofline ledger.
    Accounting must never take the encode path down."""
    try:
        _roofline.LEDGER.record(
            kernel, coder.codec.name, coder.mm, out_rows=out_rows,
            in_rows=in_rows, n=n, crc=crc, seconds=seconds,
            measured_bytes=measured_bytes)
    except Exception:  # noqa: BLE001
        pass


def _observe_call(kernel: str, coder, t0: float, *, out_rows: int,
                  in_rows: int, n: int, crc: bool = False) -> None:
    """One stopwatch per fenced coder call: the wall since `t0` goes to
    the EC stage histogram (SeaweedFS_ec_stage_seconds, with the input
    bytes) and, armed, to the roofline ledger (SeaweedFS_kernel_*_total,
    with input + output bytes); disarmed, the ledger costs one flag
    read."""
    dt = time.perf_counter() - t0
    observe_ec_stage(kernel, dt, in_rows * n)
    if _roofline.ARMED:
        _record_roofline(kernel, coder, out_rows=out_rows,
                         in_rows=in_rows, n=n, crc=crc, seconds=dt,
                         measured_bytes=(in_rows + out_rows) * n)


# Lane-dimension tile: one grid step processes k x BLOCK_N bytes.
# 8k x BLOCK_N bf16 bit planes = 80*4096*2B = 640KB VMEM for RS(10,4) —
# comfortably inside VMEM while long enough to amortize the small matmul M.
BLOCK_N = 4096


def _rs_kernel(b_ref, d_ref, o_ref, *, out_rows: int, in_rows: int,
               mm_dtype):
    """One tile: bytes (in_rows, BN) -> bytes (out_rows, BN)."""
    x = d_ref[:].astype(jnp.int32)
    # Plane-major unpack: row s*k + j is bit s of shard j. Stays 2D.
    bits = jnp.concatenate(
        [(x >> s) & 1 for s in range(8)], axis=0).astype(mm_dtype)
    acc_t = jnp.float32 if mm_dtype == jnp.bfloat16 else jnp.int32
    acc = jnp.dot(b_ref[:], bits, preferred_element_type=acc_t)
    pbits = acc.astype(jnp.int32) & 1  # sums <= 8k < 2^24: exact either way
    out = pbits[0:out_rows]
    for s in range(1, 8):
        out = out | (pbits[s * out_rows:(s + 1) * out_rows] << s)
    o_ref[:] = out.astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "in_rows", "interpret",
                                    "block_n", "mm"))
def apply_bitmatrix_pallas(bmat_pm: jax.Array, shards: jax.Array,
                           out_rows: int, in_rows: int,
                           interpret: bool = False,
                           block_n: int = BLOCK_N,
                           mm: str = "bf16") -> jax.Array:
    """(8*out_rows, 8*in_rows) plane-major bit matrix x (in_rows, n) bytes.

    n must be a multiple of block_n (the file pipeline's buffers are);
    `pad_to_block` below handles ragged tails.
    """
    n = shards.shape[1]
    grid = (n // block_n,)
    mm_dtype = jnp.bfloat16 if mm == "bf16" else jnp.int8
    kernel = functools.partial(_rs_kernel, out_rows=out_rows,
                               in_rows=in_rows, mm_dtype=mm_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * out_rows, 8 * in_rows), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((in_rows, block_n), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((out_rows, block_n), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((out_rows, n), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * out_rows * 8 * in_rows * n,
            bytes_accessed=(in_rows + out_rows) * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(bmat_pm.astype(mm_dtype), shards)


# Rows per bit plane in the CRC fold: shard rows (data then parity) are
# zero-padded to a multiple of this so every per-plane slice of the
# fold sits on a whole (16, 128) bf16 tile.
_CRC_ROW_ALIGN = 16
# CRC constants are zero-padded from 32 to one full lane tile: the MXU
# spends a whole 128-wide pass on a 32-wide operand either way, and
# full tiles keep every matmul and store in the kernel aligned.
_CRC_LANES = 128


def _rs_crc_kernel(b_ref, d_ref, w0_ref, pl_ref, pm_ref, o_ref, c_ref, *,
                   out_rows: int, in_rows: int, mm_dtype, tpb: int):
    """One tile of the CRC-fused pipeline: bytes (in_rows, BN) ->
    parity bytes (out_rows, BN), PLUS this tile's contribution to the
    CRC32-C of the `.ecc` block it lies in, for every shard row
    (in_rows data rows, then out_rows parity rows), accumulated into
    c_ref across the block's `tpb` tiles (ops/crc_fold.py has the
    algebra).  pm_ref is the tile-position-in-block shift matrix,
    selected by the grid index mod tpb, which makes the per-tile
    partials plain sums.  c_ref holds 0/1 bit COUNTS (rows x 32 live
    lanes); the caller reduces them mod 2 and packs the words."""
    x = d_ref[:].astype(jnp.int32)
    bits = jnp.concatenate(
        [(x >> s) & 1 for s in range(8)], axis=0).astype(mm_dtype)
    acc_t = jnp.float32 if mm_dtype == jnp.bfloat16 else jnp.int32
    acc = jnp.dot(b_ref[:], bits, preferred_element_type=acc_t)
    pbits = acc.astype(jnp.int32) & 1
    out = pbits[0:out_rows]
    for s in range(1, 8):
        out = out | (pbits[s * out_rows:(s + 1) * out_rows] << s)
    o_ref[:] = out.astype(jnp.uint8)

    # CRC fold, always bf16 x bf16 -> f32 (exact: operands are 0/1 and
    # every sum is <= BN < 2^24).  Planes are re-unpacked from the
    # stacked (data, parity, zero pad) rows so each plane is `rpad`
    # rows — the parity matmul's planes are in_rows apart, which no
    # tile boundary divides.
    rows = in_rows + out_rows
    rpad = c_ref.shape[0]
    stacked = [x, out]
    if rpad > rows:
        stacked.append(jnp.zeros((rpad - rows, x.shape[1]), jnp.int32))
    y = jnp.concatenate(stacked, axis=0)
    planes = jnp.concatenate(
        [(y >> s) & 1 for s in range(8)], axis=0).astype(jnp.bfloat16)
    u = jnp.dot(planes, w0_ref[:], preferred_element_type=jnp.float32)
    ub = u.astype(jnp.int32) & 1            # (8 * rpad, 128)
    fold = jnp.zeros((rpad, _CRC_LANES), jnp.float32)
    for s in range(8):
        fold = fold + jnp.dot(
            ub[s * rpad:(s + 1) * rpad].astype(jnp.bfloat16),
            pl_ref[s * _CRC_LANES:(s + 1) * _CRC_LANES],
            preferred_element_type=jnp.float32)
    vb = (fold.astype(jnp.int32) & 1).astype(jnp.bfloat16)
    sh = jnp.dot(vb, pm_ref[:], preferred_element_type=jnp.float32) \
        .astype(jnp.int32) & 1

    @pl.when(pl.program_id(0) % tpb == 0)
    def _():
        c_ref[:] = jnp.zeros_like(c_ref)

    c_ref[:] = c_ref[:] + sh


def _pad_crc_const(mat: np.ndarray) -> np.ndarray:
    """Zero-pad each of the k stacked (32, 32) CRC matrices of a
    (k*32, 32) table to a full (128, 128) tile."""
    k = mat.shape[0] // 32
    out = np.zeros((k, _CRC_LANES, _CRC_LANES), mat.dtype)
    out[:, :32, :32] = mat.reshape(k, 32, 32)
    return out.reshape(-1, _CRC_LANES)


def crc_kernel_consts(block_n: int, crc_block: int = crc_fold.BLOCK):
    """Device constants for `apply_bitmatrix_crc_pallas` at one
    (block_n, crc_block) geometry: (w0 (block_n, 128), planes_t
    (8*128, 128), posmats_t (tpb*128, 128)), bf16."""
    t = crc_fold.tables(block_n, crc_block)
    w0 = np.zeros((block_n, _CRC_LANES), np.uint8)
    w0[:, :32] = t.w0
    return (jnp.asarray(w0, jnp.bfloat16),
            jnp.asarray(_pad_crc_const(t.planes_t), jnp.bfloat16),
            jnp.asarray(_pad_crc_const(t.posmats_t), jnp.bfloat16))


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "in_rows", "interpret",
                                    "block_n", "mm", "crc_block"))
def apply_bitmatrix_crc_pallas(bmat_pm: jax.Array, shards: jax.Array,
                               w0: jax.Array, planes_t: jax.Array,
                               posmats_t: jax.Array,
                               out_rows: int, in_rows: int,
                               interpret: bool = False,
                               block_n: int = BLOCK_N,
                               mm: str = "bf16",
                               crc_block: int = crc_fold.BLOCK):
    """apply_bitmatrix_pallas plus fused `.ecc` CRC32-C: returns
    (parity (out_rows, n) uint8, crcs (in_rows + out_rows,
    n // crc_block) uint32) — the crc32c of every `.ecc` block of every
    shard row, data rows first, then parity rows.

    n must be a multiple of crc_block and the input must start on a
    `.ecc` block boundary; w0/planes_t/posmats_t come from
    `crc_kernel_consts(block_n, crc_block)`.
    """
    n = shards.shape[1]
    if n % crc_block or crc_block % block_n:
        raise ValueError(
            f"width {n} must be a multiple of the .ecc block "
            f"{crc_block}, itself a multiple of block_n {block_n}")
    grid = (n // block_n,)
    tpb = crc_block // block_n
    nb = n // crc_block
    rows = in_rows + out_rows
    rpad = -(-rows // _CRC_ROW_ALIGN) * _CRC_ROW_ALIGN
    mm_dtype = jnp.bfloat16 if mm == "bf16" else jnp.int8
    kernel = functools.partial(_rs_crc_kernel, out_rows=out_rows,
                               in_rows=in_rows, mm_dtype=mm_dtype,
                               tpb=tpb)
    parity, counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * out_rows, 8 * in_rows), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((in_rows, block_n), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, _CRC_LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8 * _CRC_LANES, _CRC_LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CRC_LANES, _CRC_LANES),
                         lambda i: (i % tpb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((out_rows, block_n), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            # One accumulator block per `.ecc` block: its index holds
            # still for tpb consecutive grid steps.
            pl.BlockSpec((rpad, _CRC_LANES), lambda i: (i // tpb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((out_rows, n), jnp.uint8),
            jax.ShapeDtypeStruct((nb * rpad, _CRC_LANES), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * out_rows * 8 * in_rows * n
            + 2 * 8 * (in_rows + out_rows) * 32 * n,
            bytes_accessed=(in_rows + out_rows) * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(bmat_pm.astype(mm_dtype), shards, w0, planes_t, posmats_t)
    bits = counts.reshape(nb, rpad, _CRC_LANES)[:, :rows, :32] & 1
    words = jnp.sum(
        bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32),
        axis=2, dtype=jnp.uint32)
    const = crc_fold.tables(block_n, crc_block).block_const
    return parity, words.T ^ jnp.uint32(const)


def pad_to_block(n: int, block_n: int = BLOCK_N) -> int:
    return -(-n // block_n) * block_n


class _Programs:
    """Which shapes of `apply_bitmatrix_pallas` the read path has
    compiled, process-wide (the jitted function's cache is): a caller
    whose shape is not ready compiles it, or waits for the thread that
    is compiling it (the warm-up at the server's start, another GET),
    and for no other shape's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ready: set = set()
        self._making: dict = {}

    def ensure(self, key, make, *args) -> None:
        """`make(*args)` compiles the program `key` names."""
        if key in self._ready:      # the hot path: one set lookup
            return
        with self._lock:
            gate = self._making.setdefault(key, threading.Lock())
        with gate:
            if key not in self._ready:
                make(*args)
                self._ready.add(key)

    def count(self) -> int:
        return len(self._ready)


READ_PROGRAMS = _Programs()


def _on_tpu() -> bool:
    """Whether JAX resolved to a TPU.  Discovery errors propagate: a
    TPU that fails to initialise must not turn into interpret mode."""
    return jaxenv.platform() == "tpu"


class PallasCoder:
    """RS coder whose byte mixing runs in the fused Pallas kernel.

    On the CPU platform (tests on the virtual CPU mesh) the kernel runs
    in interpreter mode unless `interpret=False` is forced.
    """

    def __init__(self, data_shards: int = 10, parity_shards: int = 4,
                 matrix_kind: str = "vandermonde",
                 interpret: bool | None = None,
                 mm: str | None = None, codec=None):
        from ..codecs import get_codec, rs_codec
        from .coder_jax import plane_major

        self.block_n = BLOCK_N
        # int8 is exact for 0/1 bit planes (int32 accumulation;
        # correctness-gated vs NumpyCoder in tests/test_ecpipe.py) and
        # the MXU's faster dtype; bf16 stays the off-TPU default.
        self.mm = mm or ("int8" if _on_tpu() else "bf16")
        self.codec = rs_codec(data_shards, parity_shards, matrix_kind) \
            if codec is None else get_codec(codec)
        self.data_shards = self.codec.data_shards
        self.parity_shards = self.codec.parity_shards
        self.total_shards = self.codec.total_shards
        self.matrix_kind = self.codec.matrix_kind
        self.interpret = (jaxenv.platform() == "cpu") \
            if interpret is None else interpret
        self._plane_major = plane_major
        pb = self.codec.parity_bitmatrix()
        self._parity_pm = jnp.asarray(
            plane_major(pb, self.parity_shards, self.data_shards),
            jnp.bfloat16)
        self._crc_consts = None
        # rows a read's launch gives: the most shards the scheme loses
        self.read_rows = self.total_shards - self.data_shards
        self._mats: collections.OrderedDict = collections.OrderedDict()
        self._mats_lock = threading.Lock()

    # Decode matrices kept on the device, least recently used out
    # first: a volume's loss pattern is one key a set of wanted rows.
    _MATS_KEPT = 256

    def _apply(self, mat_pm: jax.Array, shards: jax.Array,
               out_rows: int) -> jax.Array:
        n = shards.shape[1]
        padded = pad_to_block(n, self.block_n)
        if padded != n:
            shards = jnp.pad(shards, ((0, 0), (0, padded - n)))
        # in_rows follows the stacked survivors, not the scheme: a
        # minimal-read LRC decode feeds 5 rows, not data_shards.
        out = apply_bitmatrix_pallas(mat_pm, shards, out_rows,
                                     int(shards.shape[0]),
                                     interpret=self.interpret,
                                     block_n=self.block_n, mm=self.mm)
        return out[:, :n]

    @property
    def fused_crc_ok(self) -> bool:
        """True when this coder can emit `.ecc` CRC32-C values fused
        into the encode kernel (ops/crc_fold.py): the kernel tile must
        evenly divide the sidecar block."""
        return crc_fold.BLOCK % self.block_n == 0

    def _on_device(self, data) -> jax.Array:
        """`data` as a (data_shards, n) uint8 device array; for a host
        array this issues the transfer and returns."""
        data = jnp.asarray(data, jnp.uint8)
        if data.shape[0] != self.data_shards:
            raise ValueError(
                f"expected {self.data_shards} data shards, "
                f"got {data.shape[0]}")
        return data

    def _launch_crc(self, data: jax.Array) -> tuple[jax.Array, jax.Array]:
        if self._crc_consts is None:
            self._crc_consts = crc_kernel_consts(self.block_n)
        return apply_bitmatrix_crc_pallas(
            self._parity_pm, data, *self._crc_consts,
            self.parity_shards, self.data_shards,
            interpret=self.interpret, block_n=self.block_n, mm=self.mm)

    def encode_with_crc(self, data) -> tuple[jax.Array, jax.Array]:
        """Encode AND checksum in one fused kernel.

        Returns (parity (p, n) uint8, crcs (k + p, n // BLOCK) uint32)
        — the crc32c of every `.ecc` block of every shard row, rows
        ordered data shards then parity shards, exactly the shard-file
        order.  n must be a multiple of the `.ecc` block and `data`
        must start block-aligned in its shard files (the encoder's
        chunks are and do).
        """
        data = self._on_device(data)
        t0 = time.perf_counter()
        # Execution-fenced wall: a dispatch-only wall would flatter
        # the fused kernel.
        parity, crcs = jax.block_until_ready(self._launch_crc(data))
        _observe_call("encode_crc_kernel", self, t0,
                      out_rows=self.parity_shards,
                      in_rows=self.data_shards, n=int(data.shape[1]),
                      crc=True)
        return parity, crcs

    def encode(self, data) -> jax.Array:
        data = self._on_device(data)
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            self._apply(self._parity_pm, data, self.parity_shards))
        _observe_call("encode_kernel", self, t0,
                      out_rows=self.parity_shards,
                      in_rows=int(data.shape[0]), n=int(data.shape[1]))
        return out

    def encode_unfenced(self, data, crc: bool = False) -> tuple:
        """`encode` (or, with `crc`, `encode_with_crc`) for the caller
        that drains later (ec/encoder.py `_pipelined_encode`): issues
        the transfer and the kernel and returns the handles, `(parity,)`
        or `(parity, crcs)`, waiting for neither.  It records no row:
        `RooflineLedger.record` takes execution-fenced walls only, and a
        dispatch-only wall would read as an impossible rate.  A device
        error surfaces where the caller collects the handles."""
        data = self._on_device(data)
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        if crc:
            return self._launch_crc(data)
        return (self._apply(self._parity_pm, data, self.parity_shards),)

    def encode_all(self, data) -> jax.Array:
        data = jnp.asarray(data, jnp.uint8)
        return jnp.concatenate([data, self.encode(data)], axis=0)

    def _decode_mat_pm(self, present: tuple[int, ...],
                       wanted: tuple[int, ...], out_rows: int = 0):
        """(the plane-major decode matrix of a loss pattern ON THE
        DEVICE, the survivors it reads), kept after the pattern's first
        use in a bounded map on the coder: no bit-matrix build and no
        matrix transfer after it.  With `out_rows` the matrix has that
        many row planes, the wanted rows first and zeros after them:
        the read path's one shape for any number of wanted rows."""
        key = (present, wanted, out_rows)
        with self._mats_lock:
            hit = self._mats.get(key)
            if hit is not None:
                self._mats.move_to_end(key)
                return hit
        bmat, used = self.codec.decode_bitmatrix(present, wanted)
        bmat = np.asarray(bmat)
        rows = out_rows or len(wanted)
        if rows != len(wanted):
            full = np.zeros((8 * rows, bmat.shape[1]), bmat.dtype)
            full[:bmat.shape[0]] = bmat
            bmat = full
        pm = self._plane_major(bmat, rows, len(used))
        # converted on the host: the transfer is the only device work
        hit = (jax.device_put(np.asarray(pm, dtype=jnp.bfloat16)), used)
        with self._mats_lock:
            self._mats[key] = hit
            while len(self._mats) > self._MATS_KEPT:
                self._mats.popitem(last=False)
        return hit

    def _check_wanted(self, wanted) -> None:
        bad = [w for w in wanted if not 0 <= w < self.total_shards]
        if bad:
            raise ValueError(
                f"shard ids {bad} out of range [0, {self.total_shards})")

    # -- reads: one program a width ------------------------------------

    def _ensure_read_program(self, in_rows: int, width: int) -> None:
        """The read path's program for `in_rows` survivors of `width`
        bytes is compiled when this returns: by one call on zeros, as
        a read makes it, here or on the thread that came first."""
        READ_PROGRAMS.ensure(
            (in_rows, self.read_rows, width, self.block_n, self.mm,
             self.interpret), self._compile_read, in_rows, width)

    def _compile_read(self, in_rows: int, width: int) -> None:
        mat = jax.device_put(np.zeros(
            (8 * self.read_rows, 8 * in_rows), dtype=jnp.bfloat16))
        jax.block_until_ready(self._launch_read(
            mat, np.zeros((in_rows, width), np.uint8)))

    def _launch_read(self, mat_pm: jax.Array, stacked) -> jax.Array:
        return apply_bitmatrix_pallas(
            mat_pm, jnp.asarray(stacked, jnp.uint8), self.read_rows,
            int(stacked.shape[0]), interpret=self.interpret,
            block_n=self.block_n, mm=self.mm)

    def warm_reads(self) -> None:
        """Compile the read path's programs for the scheme's data
        shards as survivors, every width of READ_WIDTHS, shortest
        first (a narrower read set, LRC's group of five, compiles at
        its first read).  Off the request path: the server calls it in
        the background once it is up (ec/degraded.py)."""
        for width in READ_WIDTHS:
            self._ensure_read_program(self.data_shards, width)

    def reconstruct_padded(self, present, stacked, wanted) -> jax.Array:
        """The read path's call (ec/degraded.py), unfenced: row j of
        the (len(present), W) uint8 HOST array `stacked` is shard
        `present[j]`, ids ascending, W one of READ_WIDTHS (what lies
        past the interval's bytes may be anything: columns do not mix).
        One transfer and one launch of the width's one program; returns
        the (read_rows, W) handle whose first len(wanted) rows are the
        wanted shards, waiting for nothing.  A width whose program is
        not compiled yet is compiled here, once, whoever else asks."""
        present, wanted = tuple(present), tuple(wanted)
        self._check_wanted(wanted)
        if len(wanted) > self.read_rows:
            raise ValueError(
                f"{len(wanted)} rows wanted of a call that gives "
                f"{self.read_rows}")
        mat_pm, used = self._decode_mat_pm(present, wanted, self.read_rows)
        if used != present:
            # More survivors than the decode reads: it takes its own.
            stacked = stacked[[present.index(s) for s in used]]
        return self._read_call(mat_pm, stacked)

    def _read_call(self, mat_pm: jax.Array, stacked) -> jax.Array:
        self._ensure_read_program(*stacked.shape)
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        return self._launch_read(mat_pm, stacked)

    def reconstruct(self, shards: dict[int, jax.Array],
                    wanted: list[int] | None = None) -> dict[int, np.ndarray]:
        """The coders' common entry: `wanted` shards (default: every
        one that is not in `shards`) from the survivors, as host
        arrays.  It goes the read path's way whatever its width: the
        survivors padded to one of READ_WIDTHS in one host array, the
        kept matrix, the width's one program, wider input in pieces —
        nothing is compiled for a width.  Fenced, one
        `reconstruct_kernel` row a launch."""
        present = tuple(sorted(shards))
        if wanted is None:
            wanted = [s for s in range(self.total_shards) if s not in shards]
        self._check_wanted(wanted)
        if not wanted:
            return {}
        rows = {s: np.asarray(shards[s], dtype=np.uint8) for s in present}
        n = len(rows[present[0]])
        out = {w: np.empty(n, np.uint8) for w in wanted}
        for g in range(0, len(wanted), self.read_rows):
            group = tuple(wanted[g:g + self.read_rows])
            mat_pm, used = self._decode_mat_pm(present, group,
                                               self.read_rows)
            for off in range(0, n, READ_WIDTHS[-1]):
                take = min(n - off, READ_WIDTHS[-1])
                width = read_width(take)
                stacked = np.empty((len(used), width), np.uint8)
                for j, s in enumerate(used):
                    stacked[j, :take] = rows[s][off:off + take]
                t0 = time.perf_counter()
                rec = np.asarray(self._read_call(mat_pm, stacked))
                _observe_call("reconstruct_kernel", self, t0,
                              out_rows=self.read_rows,
                              in_rows=len(used), n=width)
                for i, w in enumerate(group):
                    out[w][off:off + take] = rec[i, :take]
        return out

    def reconstruct_unfenced(self, present, stacked, wanted) -> jax.Array:
        """`reconstruct` for the caller that drains later (ec/encoder.py
        `rebuild_ec_files`) and has the survivors in ONE host array
        already: row j of the (len(present), n) uint8 `stacked` is
        shard `present[j]`, ids ascending.  Issues one transfer and the
        kernel and returns the (len(wanted), n) handle, row i shard
        `wanted[i]`, waiting for neither: no per-shard transfer and no
        stack on the device.  Like `encode_unfenced` it records no row,
        and a device error surfaces where the caller collects the
        handle.  Its shape is the job's chunk, the same for every call
        of a job: a read, whose widths are its intervals', goes through
        `reconstruct_padded`."""
        present, wanted = tuple(present), tuple(wanted)
        self._check_wanted(wanted)
        mat_pm, used = self._decode_mat_pm(present, wanted)
        if used != present:
            # More survivors than the decode reads: it takes its own.
            stacked = stacked[[present.index(s) for s in used]]
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        return self._apply(mat_pm, jnp.asarray(stacked, jnp.uint8),
                           len(wanted))

    def verify(self, shards) -> bool:
        shards = jnp.asarray(shards, jnp.uint8)
        parity = self.encode(shards[: self.data_shards])
        return bool(jnp.array_equal(parity, shards[self.data_shards:]))
