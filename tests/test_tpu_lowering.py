"""What the TPU lowering accepts, checked without a chip.

`jax.export` for `platforms=["tpu"]` runs the Pallas -> Mosaic lowering
on the CPU: a kernel whose block shapes or ops the TPU lowering refuses
(the fused-CRC kernel's (14, 1) output block and unsigned reduction
once did) fails HERE, in tier-1, before any chip time is spent.  Every
Pallas kernel the default served path can reach is exported at the
served shapes: the (10, 4 MiB) encode chunk, 1-4 wanted rows of decode,
the 5-row LRC in-group decode — bf16 and int8, default block_n — and
the degraded read's programs at every width of READ_WIDTHS.  What
Mosaic then makes of the module only the chip can say: chip_smoke.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax import export

from seaweedfs_tpu.ec.encoder import DEFAULT_CHUNK
from seaweedfs_tpu.ops.coder_pallas import (BLOCK_N, READ_WIDTHS,
                                            apply_bitmatrix_crc_pallas,
                                            apply_bitmatrix_pallas,
                                            crc_kernel_consts)

S = jax.ShapeDtypeStruct

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
        S((8 * out_rows, 8 * in_rows), jnp.bfloat16),
        S((in_rows, DEFAULT_CHUNK), jnp.uint8))
    assert exp.platforms == ("tpu",)
    assert [a.shape for a in exp.out_avals] == [(out_rows, DEFAULT_CHUNK)]


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
        S((32, 8 * in_rows), jnp.bfloat16), S((in_rows, width), jnp.uint8))
    assert exp.platforms == ("tpu",)
    assert [a.shape for a in exp.out_avals] == [(4, width)]


@pytest.mark.parametrize("mm", ["bf16", "int8"])
def test_fused_crc_kernel_lowers_for_tpu(mm):
    consts = crc_kernel_consts(BLOCK_N)

    def fn(bmat, shards, w0, planes_t, posmats_t):
        return apply_bitmatrix_crc_pallas(
            bmat, shards, w0, planes_t, posmats_t, 4, 10,
            interpret=False, block_n=BLOCK_N, mm=mm)
    exp = export.export(jax.jit(fn), platforms=["tpu"])(
        S((32, 80), jnp.bfloat16), S((10, DEFAULT_CHUNK), jnp.uint8),
        *[S(c.shape, c.dtype) for c in consts])
    assert exp.platforms == ("tpu",)
    assert [a.shape for a in exp.out_avals] == \
        [(4, DEFAULT_CHUNK), (14, DEFAULT_CHUNK >> 20)]
