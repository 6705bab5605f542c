"""The degraded read's third rung: bytes of a shard that is gone (or
rotten), rebuilt from the survivors inside the request.

A GET of a needle in an erasure-coded volume reads its shard intervals
from the local shard, else from a remote holder, else — here — from
the codec's planned minimal survivor set (store_ec.go:322-376
recoverOneRemoteEcShardInterval).  The scrub's corrupt-block repair
takes the same rung.  What the rung does, on the thread that answers
the request:

    read.gather     the planned survivors' byte range, read straight
                    into row j of ONE pooled (survivors, W) uint8 host
                    array (local shards in place, in one call of
                    the host library, ec/volume.py `read_many`;
                    remote holders side by side on the server's
                    fan-out pool); W is the width of READ_WIDTHS that
                    holds the range
    read.dispatch   the coder's read call: one transfer of the array,
                    the launch of the width's one program, the request
                    of the copy back (a host coder reconstructs here)
    read.drain      the rows back on the host, each interval's slice
                    copied to its place in the needle's bytes

Nothing here is compiled for an interval's width: a device coder has
one program a width (ops/erasure.py READ_WIDTHS), compiled once a
process — by `warm_in_background` as the server comes up, or by the
first read that needs it — and the decode matrix of a loss pattern
stays on the device after its first use.  Lost intervals of one GET
that lie in one stripe row read the same survivors: they share one
gather and one launch with several wanted rows.

`codec.repair_plan` picks the survivors (the local group for an
in-group LRC loss, five reads), and the ladder widens to every other
sibling only when a planned read fails, stopping as soon as the
erasure pattern solves.
"""

from __future__ import annotations

import concurrent.futures
import functools
import threading
import time
from typing import Callable, NamedTuple

import numpy as np

from ..events import emit as emit_event
from ..ops.erasure import READ_WIDTHS, read_width
from ..stats import roofline as _roofline
from ..stats.metrics import ec_repair_read_bytes_total, observe_ec_stage
from ..trace import span as trace_span
from .volume import EcVolume, read_many


class Unrecoverable(Exception):
    """Too few shard intervals could be reached to solve the loss."""


class Lost(NamedTuple):
    """One shard interval the first two rungs could not read: `size`
    bytes of shard `sid` from `off`, wanted in `out` (a writable uint8
    vector of that size: the interval's place in the needle's bytes);
    `row` says which intervals read the same survivors: the stripe
    row."""
    sid: int
    off: int
    size: int
    out: np.ndarray
    row: tuple = ()


class _RowPool:
    """Free lists of (rows, W) uint8 host arrays, one list a shape,
    last in first out.  A fresh array of the widest shape is 10 MiB of
    page faults, as much as the reads into it; a kept one is paid for
    once.  `give` keeps an array only up to the bound, so a burst of
    readers never grows what the process holds between bursts: at most
    KEPT arrays a shape, 4 x 13.3 MiB of them for RS(10,4)."""

    KEPT = 4

    def __init__(self):
        self._lock = threading.Lock()
        self._free: dict[tuple[int, int], list[np.ndarray]] = {}

    def take(self, rows: int, width: int) -> np.ndarray:
        """A (rows, width) array; its contents are garbage."""
        with self._lock:
            free = self._free.get((rows, width))
            if free:
                return free.pop()
        return np.empty((rows, width), dtype=np.uint8)

    def give(self, buf: np.ndarray) -> None:
        """Hand back an array nothing reads or writes any more."""
        with self._lock:
            free = self._free.setdefault(buf.shape, [])
            if len(free) < self.KEPT:
                free.append(buf)


ROW_POOL = _RowPool()

def warm_in_background(codec=None) -> threading.Thread | None:
    """Compile the read path's programs off the request path: called
    once the server is up, where the process's coder is the device's
    (command/servers.py).  One daemon thread, shortest width first; a
    GET that arrives before its width is ready waits for that one
    program and no other (ops/coder_pallas.py READ_PROGRAMS)."""
    from ..ops.erasure import new_coder
    coder = new_coder(codec=codec)
    warm = getattr(coder, "warm_reads", None)
    if warm is None:
        return None
    th = threading.Thread(target=warm, daemon=True, name="ec-read-warm")
    th.start()
    return th


@functools.lru_cache(maxsize=1024)
def _planned(codec, candidates: tuple[int, ...], wanted: tuple[int, ...]):
    """`codec.repair_plan` for `wanted` from `candidates`, kept (a
    volume's loss pattern asks the same few plans with every GET, and
    a plan is a matrix solve in Python): (the survivors to read, ids
    ascending; whether every read stays in the wanted shards' locality
    groups), or None where the candidates cannot solve the loss."""
    try:
        plan = codec.repair_plan(candidates, list(wanted))
    except ValueError:
        return None
    return (tuple(sorted({s for p in plan for s in p.reads})),
            all(p.local for p in plan))


def groups_of(lost: list[Lost]) -> list[list[int]]:
    """Indexes of `lost` by launch: the intervals of one stripe row
    together where one gather of their union is no wider than their
    own gathers side by side, else each alone (the last bytes of one
    block and the first of the next are one row and a whole block
    apart)."""
    by_row: dict[tuple, list[int]] = {}
    for i, iv in enumerate(lost):
        by_row.setdefault(iv.row or (i,), []).append(i)
    out = []
    for members in by_row.values():
        if len(members) > 1:
            lo = min(lost[i].off for i in members)
            hi = max(lost[i].off + lost[i].size for i in members)
            apart = sum(read_width(lost[i].size) for i in members)
            if hi - lo > READ_WIDTHS[-1] or read_width(hi - lo) > apart \
                    or len({lost[i].sid for i in members}) < len(members):
                out.extend([i] for i in members)
                continue
        out.append(members)
    return out


class DegradedReader:
    """The rung, for one volume server.  What it needs of the server
    comes in as callables: `locations(vid)` (shard id -> holder urls,
    the server's cached lookup), `fetch(ev, locations, sid, off, size,
    traceparent)` (one interval from the local shard or any remote
    holder: bytes, or None), `pool()` (the fan-out executor; its tasks
    submit nothing), `node()` (this server, for events) and
    `forget(vid)` (drop a location map that let a read down)."""

    def __init__(self, locations: Callable, fetch: Callable,
                 pool: Callable, node: Callable, forget: Callable):
        self._locations = locations
        self._fetch = fetch
        self._pool = pool
        self._node = node
        self._forget = forget

    # -- what callers ask ---------------------------------------------

    def interval(self, ev: EcVolume, sid: int, off: int,
                 size: int) -> bytes:
        """One shard interval through the decode path."""
        out = np.empty(size, dtype=np.uint8)
        self.intervals(ev, [Lost(sid, off, size, out)])
        return out.tobytes()

    def intervals(self, ev: EcVolume, lost: list[Lost]) -> None:
        """Fill the `out` of every interval of `lost`: those of one
        stripe row in one launch (`groups_of`), an interval wider than
        the widest program in pieces."""
        for members in groups_of(lost):
            ivs = [lost[i] for i in members]
            if len(ivs) == 1 and ivs[0].size > READ_WIDTHS[-1]:
                iv, step = ivs[0], READ_WIDTHS[-1]
                for at in range(0, iv.size, step):
                    take = min(step, iv.size - at)
                    self._launch(ev, [Lost(iv.sid, iv.off + at, take,
                                           iv.out[at:at + take])])
            else:
                self._launch(ev, ivs)

    # -- one gather, one launch ---------------------------------------

    def _launch(self, ev: EcVolume, ivs: list[Lost]) -> None:
        """The intervals `ivs` (distinct shards, one stripe row, a
        union no wider than the widest program) from one gather of
        their union and one coder call."""
        codec = ev.codec
        wanted = tuple(iv.sid for iv in ivs)
        lo = min(iv.off for iv in ivs)
        size = max(iv.off + iv.size for iv in ivs) - lo
        clock = _roofline.StageClock(codec.name)
        t0 = time.perf_counter()
        with trace_span("ec.reconstruct", vid=ev.vid, shard=wanted[0],
                        size=size, codec=codec.name) as rspan:
            buf = None
            try:
                with clock("read.gather") as st:
                    present, buf, stacked = self._gather(
                        ev, wanted, lo, size, rspan)
                    st.add_bytes(len(present) * size)
                self._solve(ev, clock, present, stacked, wanted, lo,
                            size, ivs)
            finally:
                if buf is not None:
                    ROW_POOL.give(buf)
            rspan.set(gathered=len(present))
        _roofline.note_intervals(codec.name, len(ivs),
                                 time.perf_counter() - t0,
                                 sum(iv.size for iv in ivs))

    def _solve(self, ev, clock, present, stacked, wanted, lo, size,
               ivs) -> None:
        coder = ev.coder
        padded = getattr(coder, "reconstruct_padded", None)
        t_dev = time.perf_counter()
        with clock("read.dispatch", stacked.nbytes):
            if padded is not None:
                handle = padded(present, stacked, wanted)
                handle.copy_to_host_async()
            else:
                rec = coder.reconstruct(
                    {s: stacked[j, :size] for j, s in enumerate(present)},
                    wanted=list(wanted))
        with clock("read.drain") as st:
            if padded is not None:
                rows = np.asarray(handle)
            else:
                rows = [np.asarray(rec[s]) for s in wanted]
            # Execution-fenced: what is back on the host was computed.
            t_stage = time.perf_counter()
            observe_ec_stage("reconstruct_device", t_stage - t_dev, size)
            for i, iv in enumerate(ivs):
                iv.out[:] = rows[i][iv.off - lo:iv.off - lo + iv.size]
            st.add_bytes(sum(iv.size for iv in ivs))
            observe_ec_stage("host_staging",
                             time.perf_counter() - t_stage, size)

    def _gather(self, ev: EcVolume, wanted: tuple[int, ...], off: int,
                size: int, rspan):
        """(the survivors read, ids ascending; the pooled array to
        hand back; its (survivors, W) view whose row j holds
        `size` bytes of shard `present[j]` from `off`)."""
        codec = ev.codec
        locations = self._locations(ev.vid)
        others = [s for s in range(codec.total_shards) if s not in wanted]
        # Shards somebody is known to hold, before the plan counts on
        # them: a volume that lost four shards plans around all four.
        known = [s for s in others if s in ev.shards or locations.get(s)]
        plan = _planned(codec, tuple(known), wanted) \
            or _planned(codec, tuple(others), wanted)
        if plan is None:
            raise Unrecoverable(
                f"shards {list(wanted)} of ec volume {ev.vid} are "
                f"unrecoverable under codec {codec.name}")
        reads, in_group = plan
        width = read_width(size)
        t_gather = time.perf_counter()
        # Pool threads have no thread-local trace context: hand them
        # this span's explicitly.
        tp = rspan.traceparent() or None
        buf = ROW_POOL.take(len(reads), width)
        have: dict[int, int] = {}         # shard id -> its row of buf
        remote: dict = {}
        local = [(j, s) for j, s in enumerate(reads) if s in ev.shards]
        full = read_many([(ev.shards[s], off, buf[j, :size])
                          for j, s in local])
        have.update((s, j) for (j, s), ok in zip(local, full) if ok)
        for j, s in enumerate(reads):
            if s not in have:
                remote[self._pool().submit(
                    self._fetch, ev, locations, s, off, size, tp)] = (s, j)
        for f in concurrent.futures.as_completed(remote):
            data = f.result()
            if data is not None:
                s, j = remote[f]
                buf[j, :size] = np.frombuffer(data, dtype=np.uint8)
                have[s] = j
        present, stacked = reads, buf
        if len(have) < len(reads):
            present, stacked = self._widen(ev, wanted, others, reads,
                                           have, buf, off, size, tp)
        gathered = len(present) * size
        # Network fan-out cost, separate from the GF solve.
        observe_ec_stage("shard_gather", time.perf_counter() - t_gather,
                         gathered)
        ec_repair_read_bytes_total.inc(gathered, codec=codec.name)
        if len(have) == len(reads) and in_group:
            # Served entirely from the shard's locality group: the
            # LRC payoff.
            emit_event("ec.repair.local", node=self._node(), vid=ev.vid,
                       shard=wanted[0], codec=codec.name,
                       reads=len(present), bytes=gathered)
        return present, buf, stacked

    def _widen(self, ev, wanted, others, reads, have, buf, off, size, tp):
        """A planned read failed: every remaining sibling side by
        side, until the pattern solves.  The rows that solve it go
        into a fresh array (this is the rare way)."""
        codec = ev.codec
        locations = self._locations(ev.vid)
        got: dict[int, np.ndarray] = {s: buf[j, :size]
                                      for s, j in have.items()}

        def solved():
            try:
                return codec.decode_matrix(tuple(sorted(got)), wanted)[1]
            except ValueError:
                return None

        used = solved()
        if used is None:
            futs = {self._pool().submit(self._fetch, ev, locations, s,
                                        off, size, tp): s
                    for s in others if s not in reads}
            for f in concurrent.futures.as_completed(futs):
                data = f.result()
                if data is not None:
                    got[futs[f]] = np.frombuffer(data, dtype=np.uint8)
                    used = solved()
                    if used is not None:
                        break
            for f in futs:
                f.cancel()
        if used is None:
            # The location map let us down: the next read looks again.
            self._forget(ev.vid)
            raise Unrecoverable(
                f"cannot reconstruct shards {list(wanted)}: only "
                f"{len(got)} shard intervals reachable")
        present = tuple(sorted(used))
        stacked = np.empty((len(present), read_width(size)), np.uint8)
        for j, s in enumerate(present):
            stacked[j, :size] = got[s]
        return present, stacked
