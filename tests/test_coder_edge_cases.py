"""Edge cases surfaced in code review: empty wanted, invalid shard ids."""

import numpy as np
import pytest

from seaweedfs_tpu.ops.coder_numpy import NumpyCoder


def test_reconstruct_empty_wanted_returns_empty():
    c = NumpyCoder(10, 4)
    # Even with too few survivors, nothing wanted -> nothing to do.
    have = {i: np.zeros(10, np.uint8) for i in range(5)}
    assert c.reconstruct(have, wanted=[]) == {}


def test_reconstruct_out_of_range_wanted_raises_valueerror():
    c = NumpyCoder(10, 4)
    data = np.random.default_rng(0).integers(0, 256, (10, 20), dtype=np.uint8)
    shards = c.encode_all(data)
    have = {i: shards[i] for i in range(10)}
    with pytest.raises(ValueError, match="out of range"):
        c.reconstruct(have, wanted=[14])
    with pytest.raises(ValueError, match="out of range"):
        c.reconstruct(have, wanted=[-1])


def test_parity_only_reconstruction_skips_data_solve():
    c = NumpyCoder(10, 4)
    data = np.random.default_rng(1).integers(0, 256, (10, 64), dtype=np.uint8)
    shards = c.encode_all(data)
    have = {i: shards[i] for i in range(10)}  # all data, no parity
    rec = c.reconstruct(have)
    assert set(rec) == {10, 11, 12, 13}
    for i in rec:
        assert np.array_equal(rec[i], shards[i])


# -- switches that became constants -------------------------------------------
# Each of these environment variables once selected a path on the device
# coder's layer by hand.  Setting one now selects nothing: the default is
# what runs.

def _reconstruct_leaves_a_row(monkeypatch, tmp_path):
    from seaweedfs_tpu.ops.coder_pallas import PallasCoder
    from seaweedfs_tpu.stats import roofline
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        pc = PallasCoder(4, 2)
        full = np.asarray(pc.encode_all(np.ones((4, 1024), np.uint8)))
        pc.reconstruct({i: full[i] for i in range(1, 5)}, wanted=[0])
        assert "reconstruct_kernel" in {
            r["kernel"] for r in roofline.LEDGER.kernel_table()}
    finally:
        roofline.LEDGER.reset()


def _block_n_is_the_constant(monkeypatch, tmp_path):
    from seaweedfs_tpu.ops.coder_pallas import BLOCK_N, PallasCoder
    assert PallasCoder().block_n == BLOCK_N == 4096


def _mm_follows_the_platform(monkeypatch, tmp_path):
    from seaweedfs_tpu.ops import coder_pallas
    monkeypatch.setattr(coder_pallas, "_on_tpu", lambda: True)
    assert coder_pallas.PallasCoder(interpret=True).mm == "int8"


def _batch_encode_choices(monkeypatch) -> dict:
    """Drive `batch_encode` over one small volume of a faked cluster as
    far as the scatter's byte budget and return the depth it gave the
    stream pipeline and the cap it gave the budget."""
    import os
    from types import SimpleNamespace

    from seaweedfs_tpu.parallel import cluster_encode

    seen = {}

    class Reached(Exception):
        pass

    def fetch(tmpdir, vid, locs):
        base = os.path.join(tmpdir, str(vid))
        with open(base + ".dat", "wb") as f:
            f.write(b"\x03" + bytes(4095))
        open(base + ".idx", "wb").close()
        return base

    def run(items, dispatch, drain, depth, **kw):
        seen["depth"] = depth
        return 0

    def budget(cap):
        seen["budget"] = cap
        raise Reached

    monkeypatch.setattr(cluster_encode, "_fetch_volume", fetch)
    monkeypatch.setattr(cluster_encode, "run_pipeline", run)
    monkeypatch.setattr(cluster_encode, "_ByteBudget", budget)
    env = SimpleNamespace(data_nodes=lambda: [],
                          volume_locations=lambda vid: ["n:1"],
                          vs_call=lambda *a, **k: {})
    mesh = SimpleNamespace(shape={"vol": 1, "col": 1})
    with pytest.raises(Reached):
        cluster_encode.batch_encode(env, [7], mesh=mesh)
    return seen


def _batch_pipeline_is_two_deep(monkeypatch, tmp_path):
    assert _batch_encode_choices(monkeypatch)["depth"] == 2


def _scatter_budget_is_256_mib(monkeypatch, tmp_path):
    assert _batch_encode_choices(monkeypatch)["budget"] == 256 << 20


def _nothing_is_cached_on_disk(monkeypatch, tmp_path):
    from seaweedfs_tpu.ops.coder_pallas import PallasCoder
    from seaweedfs_tpu.stats import roofline
    monkeypatch.setattr(roofline, "_peaks", None, raising=False)
    roofline.LEDGER.reset()
    roofline.set_armed(True)
    try:
        PallasCoder(4, 2).encode(np.ones((4, 1024), np.uint8))
        roofline.debug_doc("n:1", "volume")
    finally:
        roofline.LEDGER.reset()
    assert not list(tmp_path.iterdir())


_REMOVED_SWITCHES = {
    "SEAWEEDFS_TPU_EC_PROF": ("0", _reconstruct_leaves_a_row),
    "SEAWEEDFS_TPU_BLOCK_N": ("32768", _block_n_is_the_constant),
    "SEAWEEDFS_TPU_MM": ("bf16", _mm_follows_the_platform),
    "SEAWEEDFS_TPU_EC_PIPELINE_DEPTH": ("0", _batch_pipeline_is_two_deep),
    "SEAWEEDFS_TPU_EC_SCATTER_BUDGET": ("1", _scatter_budget_is_256_mib),
    # its value was a directory: the test's own
    "SEAWEEDFS_TPU_ROOFLINE_CACHE": (None, _nothing_is_cached_on_disk),
}


@pytest.mark.parametrize("name", sorted(_REMOVED_SWITCHES))
def test_a_removed_switch_is_not_read(monkeypatch, tmp_path, name):
    value, check = _REMOVED_SWITCHES[name]
    monkeypatch.setenv(name, str(tmp_path) if value is None else value)
    check(monkeypatch, tmp_path)
