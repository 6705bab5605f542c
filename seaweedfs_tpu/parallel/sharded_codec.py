"""Mesh-sharded batched RS coding — the pod-scale EC engine.

Three entry points, all jittable over a `jax.sharding.Mesh`:

- `batched_encode`:     (V, k, N) -> (V, p, N) parity for V volumes at once.
  Volumes shard over "vol", byte columns over "col"; zero collectives.

- `batched_reconstruct`: (V, S, N) survivor stacks -> (V, W, N) rebuilt
  shards, same sharding story (the driver for `ec.rebuild` of many volumes
  — BASELINE config #3: 256 volumes on a v5e-8).

- `all_to_all_reconstruct`: survivors laid out shard-major (each chip holds
  whole shard rows, as hosts do in a cluster), internally resharded to
  column-major over ICI with `lax.all_to_all` — the SPMD equivalent of the
  reference's parallel remote-shard fetch (store_ec.go:322-376) — then
  decoded locally.  This is the design that scales to pod slices: the
  gather rides ICI, the matmul rides the MXU.

All paths share the plane-major GF(2) bit-matmul from ops/coder_jax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import rs_bitmatrix
from ..ops.coder_jax import apply_bitmatrix, plane_major
from ..utils import jaxenv


def _mm_dtype():
    """Bit-matrix matmul dtype for the batch paths: bf16 feeds the MXU
    on TPU; off-TPU, XLA emulates bf16 slowly in software while f32 is
    exactly as correct for 0/1 bit planes (counts < 2^24 accumulate
    exactly either way) and measured ~1.7x faster on the CPU backend.
    A backend that cannot initialise raises here."""
    return jnp.bfloat16 if jaxenv.platform() == "tpu" else jnp.float32


def mm_name() -> str:
    """Roofline dtype label for the batch paths' matmul dtype."""
    return "bf16" if _mm_dtype() == jnp.bfloat16 else "f32"


def record_fenced_batch(kernel: str, codec_name: str, *,
                        out_rows: int, in_rows: int, n: int,
                        batch: int, crc: bool, seconds: float,
                        measured_bytes: int | None = None,
                        node: str = "") -> None:
    """Roofline record for a batched kernel invocation.  The batch
    entry points above return ASYNC device arrays on purpose (fencing
    inside dispatch would serialize the stream pipeline), so the
    caller invokes this from its drain site, AFTER the host
    materialization that fences the kernel — `seconds` must be the
    fenced wall.  Callers gate on `roofline.ARMED` themselves so the
    disarmed cost stays one flag read."""
    try:
        from ..stats import roofline as _roofline
        _roofline.LEDGER.record(
            kernel, codec_name, mm_name(), out_rows=out_rows,
            in_rows=in_rows, n=n, batch=batch, crc=crc,
            seconds=seconds, measured_bytes=measured_bytes, node=node)
    except Exception:  # noqa: BLE001 — accounting never breaks encode
        pass


def _codec_of(data_shards: int, parity_shards: int, matrix_kind: str,
              codec):
    """Resolve the scheme: an explicit codec wins, else ad-hoc RS from
    the shard-count arguments (the pre-codec call signature)."""
    from ..codecs import get_codec, rs_codec
    if codec is None:
        return rs_codec(data_shards, parity_shards, matrix_kind)
    return get_codec(codec)


def _parity_pm(data_shards: int, parity_shards: int,
               kind: str = "vandermonde") -> np.ndarray:
    pb = rs_bitmatrix.parity_bitmatrix(
        data_shards, data_shards + parity_shards, kind)
    return plane_major(pb, parity_shards, data_shards)


@functools.partial(jax.jit, static_argnames=("parity_shards",))
def _encode_batch(bmat_pm, data, parity_shards: int):
    return jax.vmap(lambda d: apply_bitmatrix(bmat_pm, d, parity_shards))(data)


def _check_mesh_divisible(mesh: Mesh, v: int, n: int) -> None:
    if v % mesh.shape["vol"]:
        raise ValueError(
            f"batch of {v} volumes must divide over vol axis "
            f"{mesh.shape['vol']}")
    if n % mesh.shape["col"]:
        raise ValueError(
            f"byte width {n} must divide over col axis "
            f"{mesh.shape['col']}")


def _local_map(fn, mesh: Mesh):
    """shard_map a (bmat, (V_loc, R, N_loc)) -> pytree-of-(V_loc, *,
    N_loc) volume-batch function over the ("vol", "col") mesh: the bit
    matrix rides along replicated, data shards over volumes/columns.
    Every chip computes ONLY its own volume/column block — by
    construction there are ZERO collectives in the lowered program
    (asserted by tests/test_ecpipe.py on the compiled HLO).  check_vma
    stays on: every output is sharded over both axes, which the check
    confirms at trace time for free.

    Callers MUST route through the `_mapped_*` lru_cached factories
    below (never wrap a fresh closure per call): jax.jit caches by
    callable identity, so an uncached wrapper would retrace + XLA
    compile on EVERY dispatched chunk batch of the stream pipeline."""
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None), P("vol", None, "col")),
        out_specs=P("vol", None, "col"), check_vma=True))


@functools.lru_cache(maxsize=64)
def _mapped_encode(mesh: Mesh, parity_shards: int):
    return _local_map(
        lambda bmat, d: _encode_batch(bmat, d, parity_shards), mesh)


@functools.lru_cache(maxsize=64)
def _mapped_reconstruct(mesh: Mesh, wanted_count: int):
    return _local_map(
        lambda pm, s: _reconstruct_batch(pm, s, wanted_count), mesh)


def _crc_local(parity_shards: int, tile: int, block: int):
    from ..ops import crc_fold

    def fn(bmat, d):
        parity = _encode_batch(bmat, d, parity_shards)
        rows = jnp.concatenate([d, parity], axis=1)
        crcs = jax.vmap(
            lambda r: crc_fold.block_crcs_jnp(r, tile, block))(rows)
        return parity, crcs
    return fn


@functools.lru_cache(maxsize=64)
def _mapped_encode_crc(mesh: Mesh | None, parity_shards: int,
                       tile: int, block: int):
    fn = _crc_local(parity_shards, tile, block)
    if mesh is None:
        return jax.jit(fn)
    return _local_map(fn, mesh)


def _crc_reconstruct_local(wanted_count: int, tile: int, block: int):
    from ..ops import crc_fold

    def fn(pm, s):
        rebuilt = _reconstruct_batch(pm, s, wanted_count)
        crcs = jax.vmap(
            lambda r: crc_fold.block_crcs_jnp(r, tile, block))(rebuilt)
        return rebuilt, crcs
    return fn


@functools.lru_cache(maxsize=64)
def _mapped_reconstruct_crc(mesh: Mesh | None, wanted_count: int,
                            tile: int, block: int):
    fn = _crc_reconstruct_local(wanted_count, tile, block)
    if mesh is None:
        return jax.jit(fn)
    return _local_map(fn, mesh)


def batched_encode(data, mesh: Mesh | None = None,
                   data_shards: int = 10, parity_shards: int = 4,
                   matrix_kind: str = "vandermonde", codec=None):
    """(V, data_shards, N) uint8 -> (V, parity_shards, N) parity.

    With a mesh the batch runs under `shard_map` on the ("vol", "col")
    axes: volumes data-parallel over "vol", byte columns over "col",
    each chip encoding its own block with zero collectives (parity is
    columnwise for every codec, so no cross-chip bytes exist to move).
    `codec` swaps the generator matrix (e.g. "lrc"); the kernel and
    sharding story are identical.
    """
    cd = _codec_of(data_shards, parity_shards, matrix_kind, codec)
    bmat = jnp.asarray(
        plane_major(cd.parity_bitmatrix(), cd.parity_shards,
                    cd.data_shards), _mm_dtype())
    data = jnp.asarray(data, jnp.uint8)
    if mesh is None:
        return _encode_batch(bmat, data, cd.parity_shards)
    _check_mesh_divisible(mesh, data.shape[0], data.shape[2])
    data = jax.device_put(
        data, NamedSharding(mesh, P("vol", None, "col")))
    return _mapped_encode(mesh, cd.parity_shards)(bmat, data)


def batched_encode_with_crc(data, mesh: Mesh | None = None,
                            codec=None, crc_tile: int | None = None):
    """batched_encode plus per-`.ecc`-block CRC32-C of EVERY shard row
    (data rows first, then parity), computed on device in the same
    compiled step (ops/crc_fold.py).

    data: (V, k, N) uint8 with N a multiple of the `.ecc` block
    (1MB) times the mesh col axis — zero-padded tail blocks simply
    yield the crc of a zero block and are sliced off by true width.
    Returns (parity (V, p, N) uint8, crcs (V, k+p, N//BLOCK) uint32).
    """
    from ..ops import crc_fold
    cd = _codec_of(10, 4, "vandermonde", codec)
    bmat = jnp.asarray(
        plane_major(cd.parity_bitmatrix(), cd.parity_shards,
                    cd.data_shards), _mm_dtype())
    tile = crc_tile or crc_fold.JNP_TILE
    data = jnp.asarray(data, jnp.uint8)
    v, _k, n = data.shape
    block = crc_fold.BLOCK
    cols = mesh.shape["col"] if mesh is not None else 1
    if n % (block * cols):
        raise ValueError(
            f"byte width {n} must be a multiple of the .ecc block "
            f"{block} x col axis {cols}")

    fn = _mapped_encode_crc(mesh, cd.parity_shards, tile, block)
    if mesh is None:
        return fn(bmat, data)
    _check_mesh_divisible(mesh, v, n)
    data = jax.device_put(
        data, NamedSharding(mesh, P("vol", None, "col")))
    return fn(bmat, data)


@functools.partial(jax.jit, static_argnames=("wanted_count",))
def _reconstruct_batch(bmat_pm, stacked, wanted_count: int):
    return jax.vmap(
        lambda s: apply_bitmatrix(bmat_pm, s, wanted_count))(stacked)


def batched_reconstruct(stacked, present: tuple[int, ...],
                        wanted: tuple[int, ...],
                        mesh: Mesh | None = None,
                        data_shards: int = 10, parity_shards: int = 4,
                        matrix_kind: str = "vandermonde", codec=None):
    """Rebuild `wanted` shards for V volumes that all lost the same shards.

    stacked: (V, len(used), N) — the codec's `used` survivor rows
    (codec.decode_matrix(present, wanted)[1], stacked in that order)
    for each volume; for RS that is the first data_shards survivors
    sorted by id, for LRC the planned minimal read set (5 rows for an
    in-group loss).  Returns (V, len(wanted), N).
    """
    cd = _codec_of(data_shards, parity_shards, matrix_kind, codec)
    bmat, used = cd.decode_bitmatrix(tuple(present), tuple(wanted))
    pm = jnp.asarray(plane_major(np.asarray(bmat), len(wanted), len(used)),
                     _mm_dtype())
    stacked = jnp.asarray(stacked, jnp.uint8)
    if stacked.shape[1] != len(used):
        raise ValueError(
            f"stacked must carry the {len(used)} used survivor rows "
            f"({[int(u) for u in used]}), got {stacked.shape[1]}")
    if mesh is None:
        return _reconstruct_batch(pm, stacked, len(wanted))
    _check_mesh_divisible(mesh, stacked.shape[0], stacked.shape[2])
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P("vol", None, "col")))
    return _mapped_reconstruct(mesh, len(wanted))(pm, stacked)


def batched_reconstruct_with_crc(stacked, present: tuple[int, ...],
                                 wanted: tuple[int, ...],
                                 mesh: Mesh | None = None, codec=None,
                                 crc_tile: int | None = None):
    """batched_reconstruct plus per-`.ecc`-block CRC32-C of every
    REBUILT row, on device in the same compiled step — the scatter
    ships ready-made sidecar entries instead of each holder re-reading
    the pushed bytes.  Returns (rebuilt (V, W, N) uint8,
    crcs (V, W, N//BLOCK) uint32).  N must be a multiple of the `.ecc`
    block times the mesh col axis."""
    from ..ops import crc_fold
    cd = _codec_of(10, 4, "vandermonde", codec)
    bmat, used = cd.decode_bitmatrix(tuple(present), tuple(wanted))
    pm = jnp.asarray(plane_major(np.asarray(bmat), len(wanted), len(used)),
                     _mm_dtype())
    tile = crc_tile or crc_fold.JNP_TILE
    stacked = jnp.asarray(stacked, jnp.uint8)
    if stacked.shape[1] != len(used):
        raise ValueError(
            f"stacked must carry the {len(used)} used survivor rows "
            f"({[int(u) for u in used]}), got {stacked.shape[1]}")
    v, _s, n = stacked.shape
    block = crc_fold.BLOCK
    cols = mesh.shape["col"] if mesh is not None else 1
    if n % (block * cols):
        raise ValueError(
            f"byte width {n} must be a multiple of the .ecc block "
            f"{block} x col axis {cols}")

    fn = _mapped_reconstruct_crc(mesh, len(wanted), tile, block)
    if mesh is None:
        return fn(pm, stacked)
    _check_mesh_divisible(mesh, v, n)
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P("vol", None, "col")))
    return fn(pm, stacked)


def _shard_major_prep(stacked, present, wanted, mesh,
                      data_shards, parity_shards, matrix_kind):
    """Shared prologue for the shard-major reconstruction paths:
    decode bit-matrix in plane-major bf16, survivors validated and
    placed (vol, col, None) on the mesh.  Returns
    (pm, stacked, n_axis_chips, chunk_bytes)."""
    total = data_shards + parity_shards
    bmat, _used = rs_bitmatrix.decode_bitmatrix(
        data_shards, total, tuple(present), tuple(wanted), matrix_kind)
    pm = jnp.asarray(plane_major(np.asarray(bmat), len(wanted),
                                 data_shards), _mm_dtype())
    n_axis = mesh.shape["col"]
    if data_shards % n_axis != 0:
        raise ValueError(
            f"data_shards {data_shards} must divide over mesh col axis "
            f"{n_axis}")
    stacked = jnp.asarray(stacked, jnp.uint8)
    _v, s, n = stacked.shape
    if s != data_shards:
        raise ValueError(
            f"stacked must carry the {data_shards} used survivor rows, "
            f"got {s}")
    if n % n_axis != 0:
        raise ValueError(f"byte length {n} must divide over {n_axis}")
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P("vol", "col", None)))
    return pm, stacked, n_axis, n // n_axis


def all_to_all_reconstruct(stacked, present: tuple[int, ...],
                           wanted: tuple[int, ...], mesh: Mesh,
                           data_shards: int = 10, parity_shards: int = 4,
                           matrix_kind: str = "vandermonde"):
    """Reconstruction when survivors live shard-major on the mesh.

    stacked: (V, data_shards, N) placed with the *shard* axis sharded over
    the mesh's "col" axis — each chip holds complete rows (= whole shards),
    the cluster-natural layout after DMAing shards from their home hosts.
    Internally `lax.all_to_all` swaps shard-axis for column-axis over ICI
    (every chip sends each other chip its rows' slice of their columns),
    then each chip solves its column block locally and the output comes
    back column-sharded.
    """
    pm, stacked, n_shard_chips, _chunk = _shard_major_prep(
        stacked, present, wanted, mesh, data_shards, parity_shards,
        matrix_kind)
    wanted_count = len(wanted)
    s = data_shards

    def local(block):  # block: (v_loc, s/D, N) on each chip
        # Reshard: split columns D-ways, trade shard rows for column blocks.
        v_loc, s_loc, n_full = block.shape
        chunk = n_full // n_shard_chips
        parts = block.reshape(v_loc, s_loc, n_shard_chips, chunk)
        # all_to_all: concat shard axis, split column axis. -> (v, s, chunk)
        gathered = jax.lax.all_to_all(
            parts, "col", split_axis=2, concat_axis=1, tiled=False)
        gathered = gathered.reshape(v_loc, s, chunk)
        out = jax.vmap(
            lambda x: apply_bitmatrix(pm, x, wanted_count))(gathered)
        return out  # (v_loc, wanted, chunk) — column-sharded result

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=P("vol", "col", None),
        out_specs=P("vol", None, "col")))
    return fn(stacked)


def ring_reconstruct(stacked, present: tuple[int, ...],
                     wanted: tuple[int, ...], mesh: Mesh,
                     data_shards: int = 10, parity_shards: int = 4,
                     matrix_kind: str = "vandermonde"):
    """Ring-pipelined reconstruction: ppermute reduce-scatter of partial
    GF(2) products — the storage-domain analog of ring attention's
    rotate-and-accumulate (SURVEY §5 long-context mapping).

    Same input layout as `all_to_all_reconstruct` (survivor rows
    shard-major over the mesh "col" axis), but instead of resharding the
    SURVIVORS, each chip multiplies only its local rows against the
    matching column slice of the decode matrix — GF(2) linearity makes
    the full output the XOR of these partials — and the PARTIAL OUTPUTS
    ride the ring: D-1 `lax.ppermute` hops, each overlapping the next
    local XOR, until every chip holds the fully-reduced chunk for its
    own column slice.

    Traffic per chip: ring moves (D-1)/D · W·N partial bytes vs
    all_to_all's (D-1)/D · (K/D)·N survivor bytes — ring wins when
    W < K/D, i.e. rebuilding FEW shards on a SMALL mesh axis: the
    common `ec.rebuild` of one lost shard (W=1) moves 2.5x less than
    all_to_all on a D=4 axis at K=10.  Compute is also strictly local:
    each chip does 1/D of the matmul, no redundant work.
    """
    pm, stacked, n_ring, chunk = _shard_major_prep(
        stacked, present, wanted, mesh, data_shards, parity_shards,
        matrix_kind)
    wanted_count = len(wanted)
    rows_local = data_shards // n_ring

    # Plane-major columns are s*K + j; reshaped (8W, 8, K) the last axis
    # is the input-shard index, so a chip's row block [d*L, (d+1)*L) is
    # one dynamic slice.
    pm3 = pm.reshape(8 * wanted_count, 8, data_shards)

    def local(block):  # (v_loc, rows_local, N) on each chip
        d = jax.lax.axis_index("col")
        pm_local = jax.lax.dynamic_slice(
            pm3, (0, 0, d * rows_local),
            (8 * wanted_count, 8, rows_local)
        ).reshape(8 * wanted_count, 8 * rows_local)

        def partial_one(rows):  # (rows_local, N) -> (W, N) partial bytes
            return apply_bitmatrix(pm_local, rows, wanted_count)
        partial = jax.vmap(partial_one)(block)  # (v_loc, W, N)

        def take(idx):  # column chunk `idx` of the partial
            return jax.lax.dynamic_slice(
                partial, (0, 0, idx * chunk),
                (partial.shape[0], wanted_count, chunk))

        perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]
        # Ring reduce-scatter over XOR: the acc created on chip j
        # targets chunk (j-1); after D-1 hops it lands on its target
        # having absorbed every chip's contribution exactly once.
        acc = take((d - 1) % n_ring)

        def step(t, acc):
            acc = jax.lax.ppermute(acc, "col", perm)
            return jnp.bitwise_xor(acc, take((d - t - 1) % n_ring))
        acc = jax.lax.fori_loop(1, n_ring, step, acc)
        return acc  # chip d holds the reduced chunk d

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=P("vol", "col", None),
        out_specs=P("vol", None, "col")))
    return fn(stacked)


def assert_no_collectives(mesh: Mesh, parity_shards: int,
                          shape: tuple[int, int, int]) -> str:
    """Compile the sharded batch-encode step for `shape` and assert the
    HLO contains no cross-chip collectives — parity and CRCs are
    columnwise, so no cross-chip bytes should exist to move.  Returns
    the HLO text."""
    import re

    from ..codecs import get_codec

    cd = get_codec("rs")
    bmat = jnp.asarray(
        plane_major(cd.parity_bitmatrix(), parity_shards,
                    cd.data_shards), _mm_dtype())
    fn = _mapped_encode(mesh, parity_shards)
    hlo = fn.lower(bmat, jax.ShapeDtypeStruct(shape, np.uint8)) \
        .compile().as_text()
    found = re.search(
        r"all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter", hlo)
    if found:
        raise AssertionError(
            f"collective found in sharded encode HLO: {found.group(0)}")
    return hlo
