"""What the TPU lowering accepts, checked without a chip.

`jax.export` for `platforms=["tpu"]` runs the Pallas -> Mosaic lowering
on the CPU: a kernel whose block shapes or ops the TPU lowering refuses
(the fused-CRC kernel's (14, 1) output block and unsigned reduction
once did) fails HERE, in tier-1, before any chip time is spent.  Every
Pallas kernel the default served path can reach is exported at the
served shapes, in the transfer layout (`(rows, n / 512, 128)` uint32
words): the (10, 4 MiB) encode chunk, 1-4 wanted rows of decode, the
5-row LRC in-group decode — bf16 and int8, default block_n — and the
degraded read's programs at every width of READ_WIDTHS.  The chunk's
kernels are also compiled whole for a described v5e, which runs
Mosaic's own passes (tile alignment, VMEM) as the chip's compiler
would; what they then do on a chip only the chip can say.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax import export

from seaweedfs_tpu.ec.encoder import DEFAULT_CHUNK
from seaweedfs_tpu.ops.coder_pallas import (BLOCK_N, LANES, READ_WIDTHS,
                                            WORD_ROW,
                                            apply_bitmatrix_crc_pallas,
                                            apply_bitmatrix_pallas,
                                            crc_kernel_consts)


S = jax.ShapeDtypeStruct


def words(rows: int, n: int, **kw) -> jax.ShapeDtypeStruct:
    """The transfer layout of `rows` shard rows of `n` bytes."""
    return S((rows, n // WORD_ROW, LANES), jnp.uint32, **kw)


def avals(exp) -> list:
    return [S(a.shape, a.dtype) for a in exp.out_avals]


def mat(out_rows: int, in_rows: int, dtype=jnp.bfloat16, **kw):
    """A `tile_matrix` of out_rows x in_rows shards."""
    return S((64 * out_rows, 64 * in_rows), dtype, **kw)


# (out_rows, in_rows): encode, 1-4-row RS decode, LRC in-group decode.
PLAIN_SHAPES = [(4, 10), (1, 10), (2, 10), (3, 10), (1, 5)]


@pytest.mark.parametrize("mm", ["bf16", "int8"])
@pytest.mark.parametrize("out_rows,in_rows", PLAIN_SHAPES)
def test_plain_kernel_lowers_for_tpu(out_rows, in_rows, mm):
    def fn(bmat, shards):
        return apply_bitmatrix_pallas(bmat, shards, out_rows, in_rows,
                                      interpret=False, block_n=BLOCK_N,
                                      mm=mm)
    exp = export.export(jax.jit(fn), platforms=["tpu"])(
        mat(out_rows, in_rows), words(in_rows, DEFAULT_CHUNK))
    assert exp.platforms == ("tpu",)
    assert avals(exp) == [words(out_rows, DEFAULT_CHUNK)]


@pytest.mark.parametrize("in_rows", [10, 5])
@pytest.mark.parametrize("width", READ_WIDTHS)
def test_read_programs_lower_for_tpu(width, in_rows):
    """The degraded read's one program a width (four row planes out,
    whatever the loss pattern), for RS's ten survivors and the LRC
    group's five, as the chip's coder asks for it (int8)."""
    def fn(bmat, shards):
        return apply_bitmatrix_pallas(bmat, shards, 4, in_rows,
                                      interpret=False, block_n=BLOCK_N,
                                      mm="int8")
    exp = export.export(jax.jit(fn), platforms=["tpu"])(
        mat(4, in_rows, jnp.int8), words(in_rows, width))
    assert exp.platforms == ("tpu",)
    assert avals(exp) == [words(4, width)]


@pytest.mark.parametrize("mm", ["bf16", "int8"])
def test_fused_crc_kernel_lowers_for_tpu(mm):
    consts = crc_kernel_consts(BLOCK_N)

    def fn(bmat, shards, w, posmats_t):
        return apply_bitmatrix_crc_pallas(
            bmat, shards, w, posmats_t, 4, 10,
            interpret=False, block_n=BLOCK_N, mm=mm)
    exp = export.export(jax.jit(fn), platforms=["tpu"])(
        mat(4, 10), words(10, DEFAULT_CHUNK),
        *[S(c.shape, c.dtype) for c in consts])
    assert exp.platforms == ("tpu",)
    assert avals(exp) == [words(4, DEFAULT_CHUNK),
                          S((14, DEFAULT_CHUNK >> 20), jnp.uint32)]


# -- whole programs for a described chip ---------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, compiled for and never claimed.
    Made here, in a test, so that only the worker that runs this file
    loads the TPU library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel,out_rows", [
    ("seal", 4), ("rebuild", 2), ("read", 4)])
def test_chunk_kernels_compile_for_v5e(one_chip, kernel, out_rows):
    """The seal's fused-CRC encode, the rebuild's two-row decode and a
    read's four-row program at the widest read width compile for the
    chip, int8 as it runs there, and the transfer layout is the one the
    program keeps in HBM: `(8, 128)` tiles of the row-major words."""
    n = READ_WIDTHS[-1] if kernel == "read" else DEFAULT_CHUNK
    bmat = mat(out_rows, 10, jnp.int8, sharding=one_chip)
    data = words(10, n, sharding=one_chip)
    if kernel == "seal":
        consts = [S(c.shape, c.dtype, sharding=one_chip)
                  for c in crc_kernel_consts(BLOCK_N)]

        def fn(b, x, w, p):
            return apply_bitmatrix_crc_pallas(b, x, w, p, out_rows, 10,
                                              mm="int8")
        lowered = jax.jit(fn).lower(bmat, data, *consts)
    else:
        def fn(b, x):
            return apply_bitmatrix_pallas(b, x, out_rows, 10, mm="int8")
        lowered = jax.jit(fn).lower(bmat, data)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    tiled = f"u32[10,{n // WORD_ROW},128]{{2,1,0:T(8,128)}}"
    assert tiled in text, text[:400]
