"""Inputs made from --seed.  The same seed gives the same bytes; every
seed gives the same SET of sizes, in another order, so that the seed
never changes the amount of work (needle_sizes / needle_payload are
chip_smoke.py's, PR 21, with that one change).
"""

from __future__ import annotations

import http.client
import json
import threading
from urllib.parse import urlsplit

import numpy as np

from .machine import BenchFailure, check


def needle_sizes(seed: int, lo: int, hi: int, total: int) -> list[int]:
    """Needle sizes on a log-uniform grid over [lo, hi] that sum to
    `total` exactly, shuffled by the seed."""
    def grid(n: int) -> list[int]:
        return [int(lo * (hi / lo) ** ((i + 0.5) / n)) for i in range(n)]

    few, many = 1, total // lo + 2      # sum(grid(n)) grows with n
    while many - few > 1:
        mid = (few + many) // 2
        few, many = (mid, many) if sum(grid(mid)) <= total else (few, mid)
    sizes = grid(few)
    rest = total - sum(sizes)
    check(rest >= 0, f"no needle of {lo} bytes fits {total}")
    if rest:
        sizes.append(rest)
    order = np.random.default_rng([seed, 1]).permutation(len(sizes))
    return [sizes[int(i)] for i in order]


def needle_payload(seed: int, stream: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, stream, i]).bytes(size)


def request_payload(block: bytes, ident: int, size: int) -> bytes:
    """Payload `ident` of a request mix: its number, then `size - 8`
    bytes of the seed's block from an offset the number picks.  Cheap to
    make per request and to make again when the answer is checked."""
    span = len(block) - size
    off = (ident * 2654435761) % span
    return ident.to_bytes(8, "little") + block[off:off + size - 8]


def payload_block(seed: int) -> bytes:
    return np.random.default_rng([seed, 2]).bytes(1 << 16)


class Http:
    """Keep-alive connections to the master and to volume servers, for
    one thread.  The generator's own client: the program's may change."""

    def __init__(self, master: str):
        self.master = urlsplit(master).netloc
        self._conns: dict[str, http.client.HTTPConnection] = {}

    def request(self, host: str, method: str, path: str,
                body: bytes | None = None) -> bytes:
        for attempt in (0, 1):
            conn = self._conns.get(host)
            if conn is None:
                conn = self._conns[host] = http.client.HTTPConnection(
                    host, timeout=120)
            try:
                conn.request(method, path, body)
                resp = conn.getresponse()
                data = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                del self._conns[host]
                if attempt:
                    raise
                continue
            if resp.status >= 300:
                raise BenchFailure(f"{method} {host}{path}: HTTP "
                                   f"{resp.status}: {data[:200]!r}")
            return data
        raise AssertionError("unreachable")

    def assign(self, collection: str) -> tuple[str, str]:
        a = json.loads(self.request(
            self.master, "GET",
            f"/dir/assign?count=1&collection={collection}"))
        return a["fid"], a["url"]

    def write(self, collection: str, payload: bytes) -> tuple[str, str]:
        """Upstream's write: assign, then upload.  One operation."""
        fid, url = self.assign(collection)
        self.request(url, "POST", "/" + fid, payload)
        return fid, url

    def read(self, url: str, fid: str) -> bytes:
        return self.request(url, "GET", "/" + fid)

    def close(self) -> None:
        for c in self._conns.values():
            c.close()
        self._conns.clear()


def put_needles(master: str, collection: str, seed: int, stream: int,
                sizes: list[int], threads: int = 4) -> list[tuple]:
    """Upload needle i of `stream` for every size, a few at a time;
    [(fid, url, i, size)] in needle order."""
    out: list = [None] * len(sizes)
    errors: list = []
    nxt = iter(range(len(sizes)))
    lock = threading.Lock()

    def worker() -> None:
        http = Http(master)
        try:
            while True:
                with lock:
                    i = next(nxt, None)
                if i is None or errors:
                    return
                fid, url = http.write(
                    collection, needle_payload(seed, stream, i, sizes[i]))
                out[i] = (fid, url, i, sizes[i])
        except Exception as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
        finally:
            http.close()

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]
    return out
