"""Test configuration: hermetic 8-device virtual CPU mesh.

Tier-1 runs on the CPU platform: `force_cpu` pins JAX to eight virtual
CPU devices before anything imports it, so sharding/collective logic is
exercised on a mesh exactly as `__graft_entry__.dryrun_multichip` does,
Pallas kernels run in interpreter mode, and no test can claim a chip.
What the chip itself accepts is checked by `chip_smoke.py`; what the
TPU lowering accepts, without a chip, by tests/test_tpu_lowering.py.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seaweedfs_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(device_count=8)


def pytest_report_header(config):
    """Session-scoped transport toggle: SEAWEEDFS_TPU_TRANSPORT=aio
    runs every in-process AND subprocess JsonHttpServer in the suite on
    the netcore event loop (cluster/rpc.py default_transport); unset or
    "threads" is the thread-per-connection baseline.  Surfaced in the
    header so a CI log always says which transport a run exercised."""
    t = os.environ.get("SEAWEEDFS_TPU_TRANSPORT", "") or "threads"
    return f"seaweedfs_tpu transport: {t}"


@pytest.fixture(autouse=True)
def _hermetic_resilience_state():
    """Per-host circuit breakers are process-global and keyed by
    host:port; free_port() can re-issue a port a previous test drove
    into the open state.  Start every test with clean breakers (and
    leave no armed fault points behind) so failure-handling tests stay
    order-independent.  The filer chunk cache is process-global and
    keyed by fid — a fresh cluster in the next test could mint a
    colliding fid, so it resets too."""
    from seaweedfs_tpu import fault
    from seaweedfs_tpu.cluster import resilience
    from seaweedfs_tpu.stats import flows
    from seaweedfs_tpu.storage import chunk_cache
    resilience.reset_breakers()
    chunk_cache.CACHE.reset()
    # The wire-flow ledger is process-global; rows from one test's
    # cluster must not leak into the next test's conservation math.
    flows.LEDGER.reset()
    yield
    fault.disarm_all()
    resilience.reset_breakers()
    chunk_cache.CACHE.reset()
    # Tests that shrink the shared cache (streaming-memory bounds) must
    # not leak the smaller budget into the next test.
    chunk_cache.CACHE.max_bytes = chunk_cache.FilerChunkCache().max_bytes
