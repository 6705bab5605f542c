"""Device kernel ledger and the EC file pipeline's stage clock.

What a process that runs EC kernels counts about them, served at
`GET /debug/device` and merged by the master at `/cluster/device`:

- an analytic per-invocation cost model — bit-matrix geometry
  (out_rows, in_rows, n, batch) -> bytes moved, GF(2) MACs,
  arithmetic intensity — mirroring the `pl.CostEstimate` the Pallas
  kernels declare (ops/coder_pallas.py);
- a bounded invocation ring and absolute totals (count, seconds,
  bytes, work) keyed by (kernel, codec, dtype, geometry), fed by
  every execution-fenced kernel call (the fence is the caller's job —
  a dispatch-only wall would flatter the kernel);
- always-on pipeline occupancy: `cluster_encode`/`cluster_rebuild`
  hand their per-batch stage spans (stack | dispatch | device | drain)
  to `note_pipeline()`, which keeps recent gantts, publishes the
  device-occupancy fraction, names the stage that starved the device,
  and emits a `device.slow` event on sustained occupancy collapse;
- the served EC file pipeline's stage clock (`StageClock`): what the
  host thread of a seal or a rebuild is doing, stage by stage, summed
  into one row per stage beside the kernel rows of `/debug/device`,
  and opened as a `jax.profiler.TraceAnnotation` where JAX records no
  span of its own, so the same stages stand on the device trace's
  clock whenever a profiler session runs;
- the mark that an EC admin job runs in the process (`ec_job`), and
  two rows among the stage rows for the needle requests the volume
  server answered beside a job and alone (`note_request`);
- the degraded read's third rung (ec/degraded.py): its stages on the
  GET's own thread (`read.gather`, `read.dispatch`, `read.drain`), the
  intervals it rebuilt (`note_intervals`), and two request rows for
  the GETs of erasure-coded needles that did and did not reach it
  (`note_read`).

The program computes no share of a roofline: its walls are host fences
and it knows no peak of the device.  That number is the benchmark's,
from the device trace against published peaks (`benchmark/work.py`),
and it reads from here the rows' count, seconds and bytes only.

Like the other planes the kernel catalog is closed (recording an
uncataloged kernel raises), the ledger is a process singleton with
absolute rows (heartbeat rollup is idempotent), and the kill switch
(`-roofline=false` / SEAWEEDFS_TPU_ROOFLINE=0) reduces every call
site to one module-flag check.

The conservation gate, in the spirit of the wire-flow plane: analytic
bytes per invocation must match the ledger-measured bytes within
max(1%, 4KB) — a cost model that drifts from what the kernels
actually move is worse than no model.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from collections import deque

from .metrics import Counter, Gauge

# -- arming ------------------------------------------------------------------
# One module-level flag; disarmed call sites pay exactly this check
# (same discipline as fault points and metered locks, asserted by
# tests/test_roofline.py).

ARMED = os.environ.get("SEAWEEDFS_TPU_ROOFLINE", "1") not in ("0", "false")


def set_armed(on: bool) -> None:
    global ARMED
    ARMED = bool(on)


# -- kernel catalog ----------------------------------------------------------
# Closed set, like events/journal.py TYPES and flows.PURPOSES:
# RooflineLedger.record() raises on anything not listed here, so a new
# device kernel cannot ship without declaring itself (and getting a
# cost model + tests).

KERNELS = {
    "encode_kernel":
        "single-volume parity encode: bit-matrix apply on the stacked "
        "data shards (ops/coder_pallas.py PallasCoder.encode)",
    "encode_crc_kernel":
        "fused encode + per-shard CRC32 fold in one device pass "
        "(ops/coder_pallas.py PallasCoder.encode_with_crc)",
    "reconstruct_kernel":
        "decode-matrix apply rebuilding missing shards from survivors "
        "(ops/coder_pallas.py PallasCoder.reconstruct)",
    "batch_encode":
        "multi-volume sharded encode on the device mesh "
        "(parallel/sharded_codec.py batched_encode[_with_crc])",
    "batch_reconstruct":
        "multi-volume sharded rebuild on the device mesh "
        "(parallel/sharded_codec.py batched_reconstruct[_with_crc])",
}

PIPELINE_STAGES = ("stack", "dispatch", "device", "drain")

# -- stage catalog -----------------------------------------------------------
# The served EC file pipeline (ec/encoder.py, the EC admin handlers of
# cluster/volume_server.py), named by what the host thread is doing, so
# that a stage means the same with any coder and with or without the
# coder's fence.  Closed like KERNELS: StageClock raises on any other
# name.  The main-thread stages of one job are contiguous and never
# nest: their seconds sum to the job's wall (`seal.stack` and
# `beside.rebuild_read` are the read-ahead threads', `beside.seal_write`
# the seal's writer threads', beside it; the `beside.` rows are named so
# that no sum over `seal.` or `rebuild.` takes them for a main-thread
# row).  The two `req.` rows are the request plane's,
# booked by `note_request` on the threads that answer needle requests:
# what a job in the same process costs them.  The `read.` rows are a
# degraded GET's: its stages on its own thread, many threads at once,
# so their seconds sum to no wall.

STAGES = {
    "seal.stack_wait":
        "main thread blocked on the read-ahead queue: the reader did "
        "not keep up (one more count than chunks: the end-of-stream "
        "wait)",
    "seal.stack":
        "read-ahead thread, beside the main thread and in no sum: "
        "preadv of the stripe rows, several at once, in place, into a "
        "pooled (10, n) host buffer (its wait for a free buffer is "
        "outside)",
    "seal.dispatch":
        "the coder's encode call as the pipeline makes it: H2D issue, "
        "kernel launch, request of the copy back; a device coder is "
        "waited for in seal.drain, a host coder computes here",
    "seal.write_data":
        "hand-over of one chunk's data rows to the writer threads, as "
        "views of the pooled chunk, and the wait for them where they "
        "are a whole window of chunks behind (the writes themselves "
        "are beside.seal_write): what the shard writes cost the main "
        "thread",
    "seal.drain":
        "np.asarray of the parity and CRC handles of the oldest chunk "
        "in flight: collects what dispatch asked back, waits only for "
        "what is not back yet; bytes = parity + CRC bytes collected",
    "seal.write_parity":
        "hand-over of one chunk's parity rows to the writer threads, "
        "as views of the collected array, and at the end of the job "
        "the wait until the last rows are in their files (one more "
        "count than chunks)",
    "seal.finish":
        "close of the shard files, .vif, .ecc save, .ecx",
    "seal.mount":
        "first mount of a volume's shards on this server "
        "(/admin/ec/mount)",
    "seal.delete_original":
        "/admin/delete_volume of a volume whose shards are mounted",
    "rebuild.read":
        "main thread blocked on the read-ahead queue: the reader did "
        "not keep up (one more count than chunks: the end-of-stream "
        "wait); the reads themselves are beside.rebuild_read",
    "rebuild.dispatch":
        "the coder's reconstruct call as the pipeline makes it: ONE "
        "H2D of the survivors' (k, n) chunk, kernel launch, request of "
        "the copy back; a device coder is waited for in rebuild.drain, "
        "a host coder reconstructs here",
    "rebuild.drain":
        "np.asarray of the rebuilt rows of the oldest chunk in "
        "flight, dispatched REBUILD_DEPTH - 1 chunks earlier: "
        "collects what dispatch asked back, waits only for what is "
        "not back yet; bytes = rebuilt bytes collected",
    "rebuild.write":
        "CRC accumulator feed and write of one chunk's rebuilt rows, "
        "as views of the collected array",
    "rebuild.finish":
        "close of the shard files, .ecc load-modify-save",
    "rebuild.mount":
        "re-load of a mounted volume's local shards, as after a "
        "rebuild (/admin/ec/mount)",
    "beside.rebuild_read":
        "the rebuild's read-ahead thread, beside the main thread and "
        "in no sum: preadv of one chunk of every planned survivor, "
        "several at once, in place, into a pooled (k, n) host buffer "
        "(its wait for a free buffer is outside)",
    "beside.seal_write":
        "the seal's writer threads, beside the main thread and in no "
        "sum: one row of one chunk into its shard file, several "
        "threads at once, a shard file always the same thread's (CRC "
        "accumulator feed first on the non-fused path); seconds are "
        "summed over the threads",
    "read.gather":
        "a degraded read's third rung (ec/degraded.py), on the thread "
        "that answers the GET: the planned survivors' byte range read "
        "straight into the rows of one pooled (survivors, W) host "
        "buffer, W the width of ops/coder_pallas.py READ_WIDTHS that "
        "holds the range; one count a launch, bytes = gathered",
    "read.dispatch":
        "the same thread: the coder's read call "
        "(PallasCoder.reconstruct_padded: ONE H2D of the buffer, the "
        "launch of the width's one program, request of the copy back; "
        "a host coder reconstructs here); one count a launch, bytes = "
        "the buffer's",
    "read.drain":
        "the same thread: np.asarray of the launch's rows, the wait "
        "for the round trip, and the slices to the intervals' sizes; "
        "one count a launch, bytes = rebuilt bytes handed to the GET",
    "read.interval":
        "a counter beside read.dispatch, no stage of its own: count = "
        "lost shard intervals the rung rebuilt (several of one GET in "
        "one stripe row share a launch), bytes = their sizes, seconds "
        "= the rung's wall for them, gather to slices",
    "read.degraded":
        "request plane: a GET of an erasure-coded needle that reached "
        "the third rung for any of its intervals; seconds = the whole "
        "handler, locate to the needle parsed and shaped, bytes = the "
        "needle's record",
    "read.healthy":
        "the same for a GET of an erasure-coded needle whose "
        "intervals were all read from shards",
    "req.beside_job":
        "request plane, not a job's thread: a needle request (upload, "
        "read or delete on a fid path) the volume server answered "
        "while an EC admin job ran in the process, at its start or at "
        "its end; seconds = admission to the response written, bytes "
        "= the request's body",
    "req.alone":
        "the same for a needle request with no EC admin job running "
        "at either end",
}

# Stages in which JAX records nothing of its own get a TraceMe on the
# profiler's host plane.  The others are counted only: dispatch and
# drain would enclose JAX's XlaLinearize / PjitFunction / np.asarray
# spans and take their place in a per-gap attribution; seal.stack runs
# beside the main thread and would be credited with gaps it does not
# cause (seal.stack_wait is what says the reader is the bound), and
# so would beside.rebuild_read (rebuild.read is what says it) and
# beside.seal_write (seal.write_data is what says the writers are the
# bound); a request row closes hundreds of times a second on threads
# that cause no gap of the device.
ANNOTATED_STAGES = frozenset(STAGES) - {
    "seal.stack", "seal.dispatch", "seal.drain",
    "rebuild.dispatch", "rebuild.drain", "beside.rebuild_read",
    "beside.seal_write", "req.beside_job", "req.alone",
    "read.dispatch", "read.drain", "read.interval", "read.degraded",
    "read.healthy"}

kernel_seconds_total = Counter(
    "SeaweedFS_kernel_seconds_total",
    "execution-fenced device kernel wall seconds",
    ("kernel", "codec", "dtype"))

kernel_bytes_total = Counter(
    "SeaweedFS_kernel_bytes_total",
    "analytic bytes moved by device kernels (cost-model bytes; the "
    "conservation check pins these to ledger-measured bytes)",
    ("kernel", "codec", "dtype"))

kernel_work_total = Counter(
    "SeaweedFS_kernel_work_total",
    "analytic GF(2) MACs performed by device kernels",
    ("kernel", "codec", "dtype"))

device_occupancy = Gauge(
    "SeaweedFS_device_occupancy",
    "fraction of the streamed-pipeline window each stage kept the "
    "device busy (stage=device is the occupancy headline; other "
    "stages show where the wall went)",
    ("stage",))


def validate(kernel: str) -> str:
    if kernel not in KERNELS:
        raise ValueError(
            f"unknown roofline kernel {kernel!r}; cataloged: "
            f"{sorted(KERNELS)}")
    return kernel


# -- analytic cost model -----------------------------------------------------
# The bit-matrix kernels multiply an (8*out_rows x 8*in_rows) GF(2)
# matrix against 8*in_rows bit-rows of n bytes each: the same algebra
# the Pallas kernel declares in its pl.CostEstimate
# (ops/coder_pallas.py) — flops = 2 * (8*out) * (8*in) * n,
# bytes = (in + out) * n.  The fused-CRC variant folds a second
# (8*(in+out) x 32)-bit matrix over every input AND output row.


def cost_model(out_rows: int, in_rows: int, n: int, *, batch: int = 1,
               crc: bool = False) -> dict:
    """Analytic work for one kernel invocation.

    Returns bytes moved (read + written payload), GF(2) MACs (one MAC
    = one AND+XOR bit op on a byte lane), flops (2*MACs, the matmul
    convention the Pallas CostEstimate uses), and arithmetic intensity
    (flops per byte)."""
    b = int(batch)
    nbytes = (in_rows + out_rows) * n * b
    macs = 8 * out_rows * 8 * in_rows * n * b
    if crc:
        # CRC fold: 32 output bits from 8*(in+out) input bits, per
        # byte column (matches the kernel's declared estimate).
        macs += 8 * (in_rows + out_rows) * 32 * n * b
    flops = 2 * macs
    return {
        "bytes": nbytes,
        "macs": macs,
        "flops": flops,
        "intensity": flops / nbytes if nbytes else 0.0,
    }


def geometry_key(out_rows: int, in_rows: int, n: int,
                 batch: int = 1) -> str:
    if batch > 1:
        return f"{out_rows}x{in_rows}x{n}b{batch}"
    return f"{out_rows}x{in_rows}x{n}"


# -- occupancy collapse detection --------------------------------------------

_COLLAPSE_OCCUPANCY = 0.35  # device-busy fraction below this ...
_COLLAPSE_STREAK = 3        # ... for this many consecutive batches
_EMIT_EVERY = 5.0           # one device.slow event per this many s

# -- the ledger --------------------------------------------------------------

_RING_MAX = 256        # recent invocations kept for /debug/device
_PIPELINES_MAX = 16    # recent pipeline occupancy docs
_GANTT_LAST = 8        # batches of gantt carried per pipeline doc


class RooflineLedger:
    """Process-global per-kernel accounting: bounded invocation ring,
    absolute per-series totals, and recent pipeline-occupancy docs.

    The clock is injected (tests advance collapse streaks without
    sleeping); `record()` is the single kernel entry point and
    `note_pipeline()` the single occupancy entry point."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=_RING_MAX)
        # (kernel, codec, dtype, geometry) ->
        #   [count, seconds, bytes, macs]
        self._series: dict[tuple, list] = {}
        # (stage, codec) -> [count, seconds, bytes]
        self._stages: dict[tuple, list] = {}
        self._unfenced = False
        self._pipelines: deque = deque(maxlen=_PIPELINES_MAX)
        self._streak: dict[str, int] = {}
        self._collapsed: dict[str, bool] = {}
        self._last_emit = 0.0

    # -- kernel records ---------------------------------------------

    def record(self, kernel: str, codec: str, dtype: str, *,
               out_rows: int, in_rows: int, n: int, batch: int = 1,
               crc: bool = False, seconds: float,
               measured_bytes: int | None = None,
               node: str = "") -> dict:
        """One execution-fenced kernel invocation.  The caller fences
        (block_until_ready / host materialization) BEFORE stopping its
        clock; this only does the bookkeeping."""
        validate(kernel)
        cost = cost_model(out_rows, in_rows, n, batch=batch, crc=crc)
        geom = geometry_key(out_rows, in_rows, n, batch)
        secs = max(float(seconds), 1e-9)

        row = {"ts": round(self.clock(), 6), "kernel": kernel,
               "codec": codec, "dtype": dtype, "geometry": geom,
               "seconds": round(secs, 9), "bytes": cost["bytes"],
               "macs": cost["macs"], "intensity":
                   round(cost["intensity"], 3),
               "measured_bytes": measured_bytes, "node": node}
        key = (kernel, codec, dtype, geom)
        with self._lock:
            self._ring.append(row)
            series = self._series.get(key)
            if series is None:
                series = self._series[key] = [0, 0.0, 0, 0]
            series[0] += 1
            series[1] += secs
            series[2] += cost["bytes"]
            series[3] += cost["macs"]

        kernel_seconds_total.inc(secs, kernel=kernel, codec=codec,
                                 dtype=dtype)
        kernel_bytes_total.inc(cost["bytes"], kernel=kernel,
                               codec=codec, dtype=dtype)
        kernel_work_total.inc(cost["macs"], kernel=kernel, codec=codec,
                              dtype=dtype)
        return row

    # -- stage rows --------------------------------------------------

    def add_stage(self, stage: str, codec: str, seconds: float,
                  nbytes: int, count: int = 1) -> None:
        """One closed stage of the served EC file pipeline (StageClock
        is the caller), or `count` of a counter row.  Totals only: no
        ring entry."""
        with self._lock:
            row = self._stages.get((stage, codec))
            if row is None:
                row = self._stages[(stage, codec)] = [0, 0.0, 0]
            row[0] += count
            row[1] += seconds
            row[2] += nbytes

    def stage_table(self) -> list[dict]:
        """Absolute per-stage rows, `kernel` holding the stage's name:
        /debug/device lists them after the kernel rows."""
        with self._lock:
            items = sorted(self._stages.items())
        return [{"kernel": stage, "codec": codec, "count": row[0],
                 "seconds": round(row[1], 6), "bytes": row[2]}
                for (stage, codec), row in items]

    # -- pipeline occupancy -----------------------------------------

    def note_pipeline(self, kind: str, recorder, node: str = "") -> dict:
        """Fold one streamed run's recorder into the ledger: keep the
        occupancy doc + recent gantt, publish the occupancy gauge, and
        emit `device.slow` when the device-busy fraction stays
        collapsed for _COLLAPSE_STREAK consecutive runs."""
        occ = recorder.device_occupancy()
        bubbles = recorder.bubble_attribution()
        doc = {"ts": round(self.clock(), 6), "kind": kind,
               "node": node, "occupancy": occ, "bubbles": bubbles,
               "gantt": recorder.gantt(last=_GANTT_LAST)}
        frac = occ.get("fraction")
        with self._lock:
            self._pipelines.append(doc)
            collapsed = False
            if frac is not None:
                if frac < _COLLAPSE_OCCUPANCY:
                    self._streak[kind] = self._streak.get(kind, 0) + 1
                else:
                    self._streak[kind] = 0
                collapsed = self._streak[kind] >= _COLLAPSE_STREAK
                self._collapsed[kind] = collapsed
            now = self.clock()
            should_emit = (collapsed
                           and now - self._last_emit >= _EMIT_EVERY)
            if should_emit:
                self._last_emit = now

        if frac is not None:
            device_occupancy.set(frac, stage="device")
            for stage, share in (occ.get("stages") or {}).items():
                if stage != "device":
                    device_occupancy.set(share, stage=stage)
        if should_emit:
            self._emit_slow(kind, node, frac, bubbles)
        return doc

    def _emit_slow(self, kind: str, node: str, frac: float,
                   bubbles: dict) -> None:
        try:
            from ..events import emit
            from ..trace import root_span
            with root_span("device.slow", "roofline"):
                emit("device.slow", node=node, severity="warn",
                     pipeline=kind,
                     occupancy=round(float(frac), 4),
                     threshold=_COLLAPSE_OCCUPANCY,
                     streak=self._streak.get(kind, 0),
                     starving_stage=bubbles.get("starving_stage", ""),
                     bubble_seconds=round(
                         float(bubbles.get("bubble_seconds", 0.0)), 6))
        except Exception:  # noqa: BLE001 — accounting must never
            pass           # take the encode path down

    # -- conservation -----------------------------------------------

    def conservation(self) -> dict:
        """Analytic bytes vs ledger-measured bytes, per invocation in
        the ring, within max(1%, 4KB) — the cost-model correctness
        gate (PR 16 wire-flow style)."""
        checked = 0
        violations = []
        with self._lock:
            rows = list(self._ring)
        for row in rows:
            mb = row.get("measured_bytes")
            if mb is None:
                continue
            checked += 1
            tol = max(0.01 * mb, 4096.0)
            if abs(row["bytes"] - mb) > tol:
                if len(violations) < 8:
                    violations.append(
                        {"kernel": row["kernel"],
                         "geometry": row["geometry"],
                         "analytic": row["bytes"], "measured": mb})
        return {"ok": not violations, "checked": checked,
                "violations": violations}

    # -- read side ---------------------------------------------------

    def kernel_table(self) -> list[dict]:
        """Absolute per-series rollup (idempotent heartbeat rows)."""
        with self._lock:
            items = sorted(self._series.items())
        return [{"kernel": kernel, "codec": codec, "dtype": dtype,
                 "geometry": geom, "count": s[0],
                 "seconds": round(s[1], 6), "bytes": s[2], "work": s[3]}
                for (kernel, codec, dtype, geom), s in items]

    def recent(self, n: int = 32) -> list[dict]:
        with self._lock:
            return list(self._ring)[-n:]

    def pipelines(self, n: int = 4) -> list[dict]:
        with self._lock:
            return list(self._pipelines)[-n:]

    def occupancy_summary(self) -> dict:
        """Latest occupancy per pipeline kind + the collapse verdicts
        the healthz warning keys on."""
        with self._lock:
            docs = list(self._pipelines)
            collapsed = dict(self._collapsed)
            streaks = dict(self._streak)
        latest: dict[str, dict] = {}
        for doc in docs:
            occ = doc.get("occupancy") or {}
            latest[doc["kind"]] = {
                "fraction": occ.get("fraction"),
                "starving_stage":
                    (doc.get("bubbles") or {}).get("starving_stage", ""),
                "ts": doc.get("ts")}
        return {"latest": latest, "collapsed": collapsed,
                "streaks": streaks,
                "any_collapsed": any(collapsed.values())}

    def heartbeat_view(self) -> dict:
        """What a volume server ships under hb["device"]: absolute
        kernel rows (merge is idempotent) + the occupancy summary."""
        return {"kernels": self.kernel_table(),
                "occupancy": self.occupancy_summary()}

    def mark_device(self) -> None:
        """A kernel was launched whose wall nobody fenced (the EC file
        pipeline's `PallasCoder.encode_unfenced` and
        `reconstruct_unfenced`): it gets no row, but `has_rows` has its
        answer."""
        self._unfenced = True

    def has_rows(self) -> bool:
        """Whether this process has run any kernel — i.e. has a live
        JAX backend of its own."""
        with self._lock:
            return bool(self._series) or self._unfenced

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._series.clear()
            self._stages.clear()
            self._unfenced = False
            self._pipelines.clear()
            self._streak.clear()
            self._collapsed.clear()
            self._last_emit = 0.0


LEDGER = RooflineLedger()


# -- the stage clock ---------------------------------------------------------

class _Off:
    """What a disarmed StageClock hands out: nothing is timed."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def add_bytes(self, nbytes: int) -> None:
        return None


_OFF = _Off()


class _Stage:
    """One timed stage: two perf_counter() reads, one ledger row."""
    __slots__ = ("clock", "name", "nbytes", "_note", "_t0")

    def __init__(self, clock: "StageClock", name: str, nbytes: int):
        self.clock, self.name, self.nbytes = clock, name, nbytes
        self._note = None

    def add_bytes(self, nbytes: int) -> None:
        self.nbytes += nbytes

    def __enter__(self):
        if self.name in ANNOTATED_STAGES:
            # A TraceMe is live only while a profiler session is, and
            # only a process that already imported JAX can have one: a
            # role that owns no chip (utils/jaxenv.py) imports nothing
            # to annotate.
            prof = sys.modules.get("jax.profiler")
            if prof is not None:
                self._note = prof.TraceAnnotation(self.name)
                self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        self.clock._add(self.name, dt, self.nbytes)


class StageClock:
    """The stage clock of one job of the served EC file pipeline:

        clock = StageClock(codec)
        with clock("seal.drain") as st:
            ...
            st.add_bytes(parity.nbytes)

    Each closed stage adds (count 1, its seconds, its bytes) to the
    ledger's row of that name and to this job's `totals()`.  It obeys
    ARMED: disarmed, a call is that one flag check and nothing is
    timed.  A stage the catalog does not name raises."""

    def __init__(self, codec: str = ""):
        self.codec = codec
        self._lock = threading.Lock()   # stages close on several threads
        self._totals: dict[str, list] = {}

    def __call__(self, name: str, nbytes: int = 0):
        if not ARMED:
            return _OFF
        if name not in STAGES:
            raise ValueError(
                f"unknown pipeline stage {name!r}; cataloged: "
                f"{sorted(STAGES)}")
        return _Stage(self, name, nbytes)

    def _add(self, name: str, seconds: float, nbytes: int) -> None:
        LEDGER.add_stage(name, self.codec, seconds, nbytes)
        with self._lock:
            acc = self._totals.get(name)
            if acc is None:
                acc = self._totals[name] = [0, 0.0, 0]
            acc[0] += 1
            acc[1] += seconds
            acc[2] += nbytes

    def totals(self) -> dict:
        """{stage: {count, seconds, bytes}} of this job so far: what
        rides the finish event and the admin request's server span."""
        with self._lock:
            return {name: {"count": c, "seconds": round(s, 6),
                           "bytes": b}
                    for name, (c, s, b) in sorted(self._totals.items())}


# -- EC admin jobs, and the requests answered beside them ---------------------
# One process serves needles and runs EC jobs (the `server` role: the
# chip's one owner).  The mark says a job runs somewhere in the
# process; the request plane reads it at both ends of a needle request
# and books the request under one of two rows.

_jobs_lock = threading.Lock()
_jobs_running = 0


class ec_job(contextlib.ContextDecorator):
    """Around one EC admin job (a seal, a rebuild), as `with ec_job():`
    or as a decorator of its handler: the process-wide count of running
    jobs is one higher inside, and comes down however the job ends."""

    def __enter__(self):
        global _jobs_running
        with _jobs_lock:
            _jobs_running += 1
        return self

    def __exit__(self, *exc) -> None:
        global _jobs_running
        with _jobs_lock:
            _jobs_running -= 1


def jobs_running() -> int:
    return _jobs_running


def note_request(t0: float, beside: int, nbytes: int) -> None:
    """One needle request answered (cluster/rpc.py is the caller, and
    checks ARMED before it reads `t0`): `beside` is `jobs_running()` as
    the request started."""
    LEDGER.add_stage(
        "req.beside_job" if beside or _jobs_running else "req.alone",
        "", time.perf_counter() - t0, nbytes)


def note_read(t0: float, codec: str, degraded: bool,
              nbytes: int) -> None:
    """One GET of an erasure-coded needle answered
    (cluster/volume_server.py `_ec_read`, which checks ARMED before it
    reads `t0`): under `read.degraded` if any of its intervals went
    through the third rung, else under `read.healthy`."""
    LEDGER.add_stage("read.degraded" if degraded else "read.healthy",
                     codec, time.perf_counter() - t0, nbytes)


def note_intervals(codec: str, count: int, seconds: float,
                   nbytes: int) -> None:
    """`count` lost intervals of `nbytes` in all that one launch of
    the third rung rebuilt in `seconds` (ec/degraded.py)."""
    if ARMED:
        LEDGER.add_stage("read.interval", codec, seconds, nbytes, count)


def _device_memory_stats() -> list[dict]:
    """jax.local_devices() with memory stats where the backend reports
    them; empty for a process that has run no kernel: asking
    initialises the JAX backend, and a role that owns no chip
    (utils/jaxenv.py) must not claim one to answer a GET."""
    if not LEDGER.has_rows():
        return []
    import jax
    out = []
    for d in jax.local_devices():
        row = {"id": d.id, "kind": d.device_kind,
               "platform": d.platform}
        ms = d.memory_stats()   # None on backends without stats
        if ms:
            row["bytes_in_use"] = ms.get("bytes_in_use")
            row["bytes_limit"] = ms.get("bytes_limit")
        out.append(row)
    return out


def debug_doc(node: str, role: str) -> dict:
    """GET /debug/device payload: the per-kernel table followed by the
    EC file pipeline's stage rows (same list, `kernel` = the stage's
    name, no dtype, geometry or work), recent invocations, recent
    pipeline gantts with bubble attribution, the conservation verdict,
    device memory stats, the counts of the EC file pipeline's host
    buffer pool (ec/encoder.py CHUNK_POOL, under its first name), how
    the drains of the seals and of the rebuilds found the oldest chunk
    in flight (SEAL_INFLIGHT, REBUILD_INFLIGHT: `ready` or `waited`)
    and how the seals' hand-overs found the writer threads
    (SEAL_WRITER), which way a GET's shard reads go (`ec_reads`:
    ec/volume.py `read_many_path`), and how the device coder's transfers
    crossed (`coder_layout`: ops/erasure.py CODER_LAYOUT)."""
    from ..ec import encoder, volume
    from ..ops import erasure
    return {"node": node, "role": role, "armed": ARMED,
            "kernels": LEDGER.kernel_table() + LEDGER.stage_table(),
            "recent": LEDGER.recent(16),
            "pipelines": LEDGER.pipelines(4),
            "occupancy": LEDGER.occupancy_summary(),
            "conservation": LEDGER.conservation(),
            "devices": _device_memory_stats(),
            "seal_buffers": encoder.CHUNK_POOL.counts(),
            "seal_inflight": encoder.SEAL_INFLIGHT.counts(),
            "seal_writer": encoder.SEAL_WRITER.counts(),
            "rebuild_inflight": encoder.REBUILD_INFLIGHT.counts(),
            "ec_reads": volume.read_many_path(),
            "coder_layout": erasure.CODER_LAYOUT.counts()}
