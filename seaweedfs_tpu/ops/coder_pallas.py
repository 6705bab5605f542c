"""Fused Pallas TPU kernel for Reed-Solomon GF(2^8) coding on the MXU.

The XLA path (`coder_jax.py`) materializes the unpacked bit planes (an 8x
expansion of the data) in HBM between the unpack and the matmul.  This
kernel fuses the whole pipeline per tile in VMEM:

    HBM --(k, 8, 128) words--> VMEM
        unpack to (32 * 8k, 128) bit planes      (VPU shifts)
        (64r, 64k) @ (64k, 512) matmul           (MXU)
        mod-2 + pack to (r, 8, 128) words        (VPU)
    VMEM --(r, 8, 128) words--> HBM

so HBM traffic stays at bytes-in + bytes-out while the GF math runs at MXU
rate.  This is the TPU replacement for klauspost/reedsolomon's AVX2 galois
kernels (reference hot loop: weed/storage/erasure_coding/ec_encoder.go:162,
store_ec.go:322).

The bytes cross as they lie: a `(rows, n)` uint8 host chunk is sent as
the `(rows, n / 512, 128)` uint32 view of its memory, whose (8, 128)
tiled device layout IS the host's row-major order, so the runtime
relayouts nothing (a uint8 array is padded to 32-row tiles on the host,
and back); results come back so and are viewed as bytes (`host_rows`).
Coding is column-wise, so no byte moves in the kernel: bit 8b + s of a
word is bit s of its byte lane b, and each byte lane and each of a
tile's eight sublane rows is a set of columns of its own
(`tile_matrix`).

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
from .erasure import CODER_LAYOUT, READ_WIDTHS, host_rows, read_width


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


# Bytes of each row one grid step processes: one (8, 128) tile of words.
BLOCK_N = 4096
# The transfer layout: a row is n / WORD_ROW sublane rows of LANES words.
LANES = 128
WORD_ROW = 4 * LANES
# Sublane rows of one (8, 128) 32-bit tile.
_SUB = 8


def host_words(rows) -> np.ndarray:
    """The `(rows, n)` uint8 host array as `(rows, n / WORD_ROW, LANES)`
    uint32 words, a view of its memory; a width that is no whole number
    of blocks is copied into a padded array first (counted)."""
    rows = np.asarray(rows, dtype=np.uint8)
    pad = -rows.shape[1] % BLOCK_N
    if pad:
        rows = np.pad(rows, ((0, 0), (0, pad)))
    CODER_LAYOUT.note_in(rows.nbytes if pad else 0)
    return rows.view(np.uint32).reshape(rows.shape[0], -1, LANES)


def _planes(x: jax.Array, mm_dtype) -> jax.Array:
    """(R, 128) int32 words -> (8R, 512) bit planes: row s*R + i holds
    bit s of row i's bytes, byte lane b in lanes 128b..128b+127."""
    return jnp.concatenate(
        [jnp.concatenate([(x >> (8 * b + s)) & 1 for s in range(8)],
                         axis=0) for b in range(4)],
        axis=1).astype(mm_dtype)


def _parity(b_ref, x: jax.Array, out_rows: int, mm_dtype) -> jax.Array:
    """One tile: (in_rows * 8, 128) int32 words, row 8j + t sublane t of
    row j -> (out_rows * 8, 128) words of the product."""
    acc_t = jnp.float32 if mm_dtype == jnp.bfloat16 else jnp.int32
    acc = jnp.dot(b_ref[...], _planes(x, mm_dtype),
                  preferred_element_type=acc_t)
    pbits = acc.astype(jnp.int32) & 1  # sums <= 8k < 2^24: exact either way
    m = out_rows * _SUB
    byte = pbits[0:m]
    for s in range(1, 8):
        byte = byte | (pbits[s * m:(s + 1) * m] << s)
    out = byte[:, 0:LANES]
    for b in range(1, 4):
        out = out | (byte[:, b * LANES:(b + 1) * LANES] << (8 * b))
    return out


def _words_of(block: jax.Array) -> jax.Array:
    """A (rows, sub, 128) uint32 block as (rows * sub, 128) int32."""
    rows, sub, lanes = block.shape
    return pltpu.bitcast(block.reshape(rows * sub, lanes), jnp.int32)


def _rs_kernel(b_ref, d_ref, o_ref, *, out_rows: int, mm_dtype) -> None:
    """One block: words (in_rows, sub, 128) -> words (out_rows, sub,
    128), one (8, 128) tile of every row at a time."""
    for t in range(0, d_ref.shape[1], _SUB):
        out = _parity(b_ref, _words_of(d_ref[:, t:t + _SUB, :]),
                      out_rows, mm_dtype)
        o_ref[:, t:t + _SUB, :] = pltpu.bitcast(out, jnp.uint32) \
            .reshape(out_rows, _SUB, LANES)


_MM_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


def tile_matrix(bmat, mm: str = "bf16") -> np.ndarray:
    """The kernels' matrix, made once on the host: the (8r, 8k)
    plane-major 0/1 bit matrix -> (64r, 64k) in the matmul's dtype, row
    8i + t and column 8j + t holding bmat[i, j], so one matmul codes
    each of a tile's eight sublane rows (the planes' row 8j + t,
    `_planes`) apart from the others."""
    return np.kron(np.asarray(bmat, np.float32),
                   np.eye(_SUB, dtype=np.float32)).astype(_MM_DTYPES[mm])


def _block_spec(rows: int, block_n: int) -> pl.BlockSpec:
    return pl.BlockSpec((rows, block_n // WORD_ROW, LANES),
                        lambda i: (0, i, 0), memory_space=pltpu.VMEM)


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "in_rows", "interpret",
                                    "block_n", "mm"))
def apply_bitmatrix_pallas(bmat: jax.Array, words: jax.Array,
                           out_rows: int, in_rows: int,
                           interpret: bool = False,
                           block_n: int = BLOCK_N,
                           mm: str = "bf16") -> jax.Array:
    """`tile_matrix` of an (8*out_rows, 8*in_rows) plane-major bit
    matrix x (in_rows, n) bytes, both ways in the transfer layout:
    `words` (in_rows, n / WORD_ROW, LANES) uint32 -> (out_rows, n /
    WORD_ROW, LANES) uint32.  n must be a multiple of block_n, itself
    of BLOCK_N (`host_words` pads a host array)."""
    subs = words.shape[1]
    mm_dtype = _MM_DTYPES[mm]
    kernel = functools.partial(_rs_kernel, out_rows=out_rows,
                               mm_dtype=mm_dtype)
    n = subs * WORD_ROW
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((64 * out_rows, 64 * in_rows), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            _block_spec(in_rows, block_n),
        ],
        out_specs=_block_spec(out_rows, block_n),
        out_shape=jax.ShapeDtypeStruct((out_rows, subs, LANES), jnp.uint32),
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * out_rows * 8 * in_rows * n,
            bytes_accessed=(in_rows + out_rows) * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(bmat.astype(mm_dtype), words)


# Shard rows (data then parity) of the CRC fold are zero-padded to a
# multiple of this, so every bit plane's slice of the fold's (rows * 8,
# 128) words sits on whole (16, 128) bf16 tiles.
_CRC_ROW_ALIGN = 2
# CRC constants are zero-padded from 32 to one full lane tile: the MXU
# spends a whole 128-wide pass on a 32-wide operand either way, and
# full tiles keep every matmul and store in the kernel aligned.
_CRC_LANES = 128


def _rs_crc_kernel(b_ref, d_ref, w_ref, pm_ref, o_ref, c_ref, *,
                   out_rows: int, mm_dtype, tpb: int):
    """One block of the CRC-fused pipeline: words (in_rows, sub, 128) ->
    parity words (out_rows, sub, 128), PLUS every sublane row's
    contribution to the CRC32-C of the `.ecc` block it lies in, for
    every shard row (data rows, then parity rows), accumulated into
    c_ref across the `.ecc` block's `tpb` grid steps (ops/crc_fold.py
    has the algebra).  A sublane row is 512 bytes of one shard row:
    w_ref holds the 512-byte weights of every (bit, byte lane, lane)
    with the bit-plane fold applied, so one matmul gives each sublane
    row's partial as if it ended its tile; pm_ref is the tile-in-block
    shift matrix, selected by the grid index mod tpb; the sublane
    row's own place in its tile is applied by the caller.  c_ref holds
    0/1 bit COUNTS (row 8j + t: sublane t of shard row j; 32 live
    lanes); the caller reduces them mod 2 and packs the words."""
    _rs_kernel(b_ref, d_ref, o_ref, out_rows=out_rows, mm_dtype=mm_dtype)

    # CRC fold, bf16 x bf16 -> f32 (exact: operands are 0/1 and every
    # sum is <= 8 * 512 < 2^24).
    x, out = _words_of(d_ref[...]), _words_of(o_ref[...])
    m = c_ref.shape[0]
    stacked = [x, out]
    if m > x.shape[0] + out.shape[0]:
        stacked.append(jnp.zeros((m - x.shape[0] - out.shape[0], LANES),
                                 jnp.int32))
    y = jnp.concatenate(stacked, axis=0)
    planes = jnp.concatenate(
        [(y >> (8 * b + s)) & 1 for s in range(8) for b in range(4)],
        axis=1).astype(jnp.bfloat16)
    u = jnp.dot(planes, w_ref[...], preferred_element_type=jnp.float32)
    ub = (u.astype(jnp.int32) & 1).astype(jnp.bfloat16)
    sh = jnp.dot(ub, pm_ref[...], preferred_element_type=jnp.float32) \
        .astype(jnp.int32) & 1

    @pl.when(pl.program_id(0) % tpb == 0)
    def _():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] = c_ref[...] + sh


def _pad_crc_const(mat: np.ndarray) -> np.ndarray:
    """Zero-pad each of the k stacked (32, 32) CRC matrices of a
    (k*32, 32) table to a full (128, 128) tile."""
    k = mat.shape[0] // 32
    out = np.zeros((k, _CRC_LANES, _CRC_LANES), mat.dtype)
    out[:, :32, :32] = mat.reshape(k, 32, 32)
    return out.reshape(-1, _CRC_LANES)


def crc_kernel_consts(block_n: int, crc_block: int = crc_fold.BLOCK):
    """Device constants for `apply_bitmatrix_crc_pallas` at one
    (block_n, crc_block) geometry: (w (32*128, 128), posmats_t
    (tpb*128, 128)), bf16.  Row 512s + 128b + l of w is the weight of
    bit s of byte 4l + b of a 512-byte sublane row — the word layout's
    byte order — folded through the bit plane's map A_s."""
    row = crc_fold.tables(WORD_ROW, crc_block)
    t = crc_fold.tables(block_n, crc_block)
    w = np.zeros((8, 4, LANES, _CRC_LANES), np.uint8)
    for s in range(8):
        ws = row.w0.astype(np.int64) @ t.planes[s].T.astype(np.int64) % 2
        w[s, :, :, :32] = ws.reshape(LANES, 4, 32).transpose(1, 0, 2)
    return (jax.device_put(w.reshape(-1, _CRC_LANES).astype(jnp.bfloat16)),
            jax.device_put(_pad_crc_const(t.posmats_t).astype(jnp.bfloat16)))


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "in_rows", "interpret",
                                    "block_n", "mm", "crc_block"))
def apply_bitmatrix_crc_pallas(bmat: jax.Array, words: jax.Array,
                               w: jax.Array, posmats_t: jax.Array,
                               out_rows: int, in_rows: int,
                               interpret: bool = False,
                               block_n: int = BLOCK_N,
                               mm: str = "bf16",
                               crc_block: int = crc_fold.BLOCK):
    """apply_bitmatrix_pallas plus fused `.ecc` CRC32-C: returns
    (parity words (out_rows, n / WORD_ROW, LANES) uint32, crcs (in_rows
    + out_rows, n // crc_block) uint32) — the crc32c of every `.ecc`
    block of every shard row, data rows first, then parity rows.

    n must be a multiple of crc_block and the input must start on a
    `.ecc` block boundary; w/posmats_t come from
    `crc_kernel_consts(block_n, crc_block)`.
    """
    subs = words.shape[1]
    n = subs * WORD_ROW
    if n % crc_block or crc_block % block_n:
        raise ValueError(
            f"width {n} must be a multiple of the .ecc block "
            f"{crc_block}, itself a multiple of block_n {block_n}")
    sub = block_n // WORD_ROW
    tpb = crc_block // block_n
    nb = n // crc_block
    rows = in_rows + out_rows
    rpad = -(-rows // _CRC_ROW_ALIGN) * _CRC_ROW_ALIGN
    mm_dtype = _MM_DTYPES[mm]
    kernel = functools.partial(_rs_crc_kernel, out_rows=out_rows,
                               mm_dtype=mm_dtype, tpb=tpb)
    parity, counts = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((64 * out_rows, 64 * in_rows), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            _block_spec(in_rows, block_n),
            pl.BlockSpec((32 * LANES, _CRC_LANES), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_CRC_LANES, _CRC_LANES),
                         lambda i: (i % tpb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            _block_spec(out_rows, block_n),
            # One accumulator block per `.ecc` block: its index holds
            # still for tpb consecutive grid steps.
            pl.BlockSpec((rpad * sub, _CRC_LANES), lambda i: (i // tpb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((out_rows, subs, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((nb * rpad * sub, _CRC_LANES), jnp.int32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * out_rows * 8 * in_rows * n
            + 2 * 8 * (in_rows + out_rows) * 32 * n,
            bytes_accessed=(in_rows + out_rows) * n,
            transcendentals=0,
        ),
        interpret=interpret,
    )(bmat.astype(mm_dtype), words, w, posmats_t)
    bits = counts.reshape(nb, rpad, sub, _CRC_LANES)[:, :rows, :, :32] & 1
    # Sublane row t's own place in its tile: 512 * (sub - 1 - t) bytes
    # of zeros follow it there.
    shift = crc_fold.tables(WORD_ROW, crc_block).posmats[-sub:]
    state = jnp.einsum("brti,toi->bro", bits,
                       jnp.asarray(shift, jnp.int32)) & 1
    crcs = jnp.sum(
        state.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32),
        axis=2, dtype=jnp.uint32)
    const = crc_fold.tables(block_n, crc_block).block_const
    return parity, crcs.T ^ jnp.uint32(const)


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
        self._parity_mat = jax.device_put(tile_matrix(
            plane_major(pb, self.parity_shards, self.data_shards), self.mm))
        self._crc_consts = None
        # rows a read's launch gives: the most shards the scheme loses
        self.read_rows = self.total_shards - self.data_shards
        self._mats: collections.OrderedDict = collections.OrderedDict()
        self._mats_lock = threading.Lock()

    # Decode matrices kept on the device, least recently used out
    # first: a volume's loss pattern is one key a set of wanted rows.
    _MATS_KEPT = 256

    def _apply(self, mat: jax.Array, words: jax.Array,
               out_rows: int) -> jax.Array:
        # in_rows follows the stacked survivors, not the scheme: a
        # minimal-read LRC decode feeds 5 rows, not data_shards.
        return apply_bitmatrix_pallas(mat, words, out_rows,
                                      int(words.shape[0]),
                                      interpret=self.interpret,
                                      block_n=self.block_n, mm=self.mm)

    @staticmethod
    def _send(rows) -> jax.Array:
        """Issues the transfer of the host rows as words and returns."""
        return jax.device_put(host_words(rows))

    @staticmethod
    def _bytes_of(words: jax.Array, n: int):
        """The handle to drain: the words, or for a padded width the
        `(rows, n)` bytes on the host, waited for."""
        return words if n % BLOCK_N == 0 else host_rows(words)[:, :n]

    @property
    def fused_crc_ok(self) -> bool:
        """True when this coder can emit `.ecc` CRC32-C values fused
        into the encode kernel (ops/crc_fold.py): the kernel tile must
        evenly divide the sidecar block."""
        return crc_fold.BLOCK % self.block_n == 0

    def _on_device(self, data) -> jax.Array:
        """`data` as the (data_shards, n / WORD_ROW, LANES) words on the
        device; issues the transfer and returns."""
        data = np.asarray(data, np.uint8)
        if data.shape[0] != self.data_shards:
            raise ValueError(
                f"expected {self.data_shards} data shards, "
                f"got {data.shape[0]}")
        return self._send(data)

    def _launch_crc(self, words: jax.Array) -> tuple[jax.Array, jax.Array]:
        if self._crc_consts is None:
            self._crc_consts = crc_kernel_consts(self.block_n)
        return apply_bitmatrix_crc_pallas(
            self._parity_mat, words, *self._crc_consts,
            self.parity_shards, self.data_shards,
            interpret=self.interpret, block_n=self.block_n, mm=self.mm)

    def encode_with_crc(self, data) -> tuple[np.ndarray, jax.Array]:
        """Encode AND checksum in one fused kernel.

        Returns (parity (p, n) uint8, crcs (k + p, n // BLOCK) uint32)
        — the crc32c of every `.ecc` block of every shard row, rows
        ordered data shards then parity shards, exactly the shard-file
        order.  n must be a multiple of the `.ecc` block and `data`
        must start block-aligned in its shard files (the encoder's
        chunks are and do).
        """
        n = np.shape(data)[1]
        if n % crc_fold.BLOCK:
            raise ValueError(
                f"width {n} must be a multiple of the .ecc block "
                f"{crc_fold.BLOCK}")
        words = self._on_device(data)
        t0 = time.perf_counter()
        # Execution-fenced wall: a dispatch-only wall would flatter
        # the fused kernel.
        parity, crcs = jax.block_until_ready(self._launch_crc(words))
        _observe_call("encode_crc_kernel", self, t0,
                      out_rows=self.parity_shards,
                      in_rows=self.data_shards, n=n, crc=True)
        return host_rows(parity), crcs

    def encode(self, data) -> np.ndarray:
        n = np.shape(data)[1]
        words = self._on_device(data)
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            self._apply(self._parity_mat, words, self.parity_shards))
        _observe_call("encode_kernel", self, t0,
                      out_rows=self.parity_shards,
                      in_rows=self.data_shards, n=n)
        return host_rows(out)[:, :n]

    def encode_unfenced(self, data, crc: bool = False) -> tuple:
        """`encode` (or, with `crc`, `encode_with_crc`) for the caller
        that drains later (ec/encoder.py `_pipelined_encode`): issues
        the transfer and the kernel and returns the handles, `(parity,)`
        or `(parity, crcs)`, waiting for neither; the parity handle is
        the words, which `host_rows` views as bytes once drained.  It
        records no row: `RooflineLedger.record` takes execution-fenced
        walls only, and a dispatch-only wall would read as an impossible
        rate.  A device error surfaces where the caller collects the
        handles."""
        n = np.shape(data)[1]
        words = self._on_device(data)
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        if crc:
            return self._launch_crc(words)
        return (self._bytes_of(
            self._apply(self._parity_mat, words, self.parity_shards), n),)

    def encode_all(self, data) -> np.ndarray:
        data = np.asarray(data, np.uint8)
        return np.concatenate([data, self.encode(data)], axis=0)

    def _decode_mat(self, present: tuple[int, ...],
                       wanted: tuple[int, ...], out_rows: int = 0):
        """(the tiled decode matrix of a loss pattern ON THE DEVICE, the
        survivors it reads), kept after the pattern's first
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
        # tiled on the host: the transfer is the only device work
        hit = (jax.device_put(tile_matrix(pm, self.mm)), used)
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
        # zeros sent from the host: a device-side `jnp.zeros` would be
        # one more program to compile
        mat = jax.device_put(np.zeros((64 * self.read_rows, 64 * in_rows),
                                      _MM_DTYPES[self.mm]))
        jax.block_until_ready(self._launch_read(mat, jax.device_put(
            np.zeros((in_rows, width // WORD_ROW, LANES), np.uint32))))

    def _launch_read(self, mat: jax.Array, words: jax.Array) -> jax.Array:
        return apply_bitmatrix_pallas(
            mat, words, self.read_rows, int(words.shape[0]),
            interpret=self.interpret, block_n=self.block_n, mm=self.mm)

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
        the handle of the (read_rows, W) words (`host_rows` views them
        as bytes) whose first len(wanted) rows are the wanted shards,
        waiting for nothing.  A width whose program is
        not compiled yet is compiled here, once, whoever else asks."""
        present, wanted = tuple(present), tuple(wanted)
        self._check_wanted(wanted)
        if len(wanted) > self.read_rows:
            raise ValueError(
                f"{len(wanted)} rows wanted of a call that gives "
                f"{self.read_rows}")
        mat, used = self._decode_mat(present, wanted, self.read_rows)
        if used != present:
            # More survivors than the decode reads: it takes its own.
            stacked = stacked[[present.index(s) for s in used]]
        return self._read_call(mat, stacked)

    def _read_call(self, mat: jax.Array, stacked) -> jax.Array:
        self._ensure_read_program(*stacked.shape)
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        return self._launch_read(mat, self._send(stacked))

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
            mat, used = self._decode_mat(present, group,
                                               self.read_rows)
            for off in range(0, n, READ_WIDTHS[-1]):
                take = min(n - off, READ_WIDTHS[-1])
                width = read_width(take)
                stacked = np.empty((len(used), width), np.uint8)
                for j, s in enumerate(used):
                    stacked[j, :take] = rows[s][off:off + take]
                t0 = time.perf_counter()
                rec = host_rows(self._read_call(mat, stacked))
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
        kernel and returns the handle of the (len(wanted), n) words
        (`host_rows` views them as bytes), row i shard `wanted[i]`,
        waiting for neither: no per-shard transfer and no stack on the
        device.  Like `encode_unfenced` it records no row,
        and a device error surfaces where the caller collects the
        handle.  Its shape is the job's chunk, the same for every call
        of a job: a read, whose widths are its intervals', goes through
        `reconstruct_padded`."""
        present, wanted = tuple(present), tuple(wanted)
        self._check_wanted(wanted)
        mat, used = self._decode_mat(present, wanted)
        if used != present:
            # More survivors than the decode reads: it takes its own.
            stacked = stacked[[present.index(s) for s in used]]
        if _roofline.ARMED:
            _roofline.LEDGER.mark_device()
        return self._bytes_of(
            self._apply(mat, self._send(stacked), len(wanted)),
            stacked.shape[1])

    def verify(self, shards) -> bool:
        shards = np.asarray(shards, np.uint8)
        parity = self.encode(shards[: self.data_shards])
        return bool(np.array_equal(parity, shards[self.data_shards:]))
