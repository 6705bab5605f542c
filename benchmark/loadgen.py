"""The request generator: closed-loop clients in worker processes, the
layout of upstream's `weed benchmark` (copied in spirit from
seaweedfs_tpu/command/benchmark_cmd.py, not imported: the program may
change, the yardstick may not).

One general generator reads a mix's parameters: operation, payload size,
clients, processes, key choice.  Each client is one thread with its own
keep-alive connections; it sends its next request when the last one has
been answered.  Everything a request needs is made before the window
opens: connections, the payload block, the order of keys.  Per request
the loop allocates the payload and two clock readings, and writes into
arrays that were there before.  The parent runs nothing else during the
window.

A read mix has one of two key sets.  Its own, written in set-up with
this generator (`keys`: ids, fids, the server), every key `size` bytes
and checked against `request_payload`.  Or, with `keys_from: "pool"`,
every needle of every volume of the jobs' pool (`keys`: the pool's
volume ids and, once for them all, each needle's key+cookie, its number
in the seed's stream and its size; key `v * needles + j` is needle j of
volume v): the answer is held against `needle_payload` of the seed, the
bytes that were put.  A worker makes those bytes once, before it says
`ready`, and keeps a 128-bit BLAKE2b digest and the length of each: 16
bytes a needle where the payloads themselves are the whole volume (520
MiB, in each of four workers), for about a second of each worker's CPU
in set-up (all at once) and, per answer, one hash of it, outside the
interpreter lock, where the payloads would cost a compare.

A worker is `python loadgen.py <spec.json>`; it writes `<out>.npz`.
It says `ready` once it has imported, read its keys and made its
arrays, and starts its clients when the parent, having heard every
worker, sends the instant the window opens: no worker is still starting
while the window runs.  All read one clock, CLOCK_MONOTONIC.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark.data import (Http, needle_payload, payload_block,  # noqa: E402
                            request_payload)
from benchmark.machine import check  # noqa: E402

FID_BYTES = 32
GOOD, DIFFERS, FAILED = 0, 1, 2        # how a request ended: `ok`


def _client(spec: dict, thread: int, block: bytes, keys, arrays: dict,
            stop_at: float) -> None:
    """One closed-loop client.  Fills its row of `arrays` until
    `stop_at` (or `count` requests, for a key set written in set-up)."""
    http = Http(spec["master"])
    op, size, coll = spec["op"], spec["size"], spec["collection"]
    client_no = spec["worker"] * spec["threads"] + thread
    done, lat, ok = arrays["done"][thread], arrays["lat"][thread], \
        arrays["ok"][thread]
    ids, fids = arrays["ids"][thread], arrays["fids"][thread]
    count = spec.get("count") or len(done)
    url = ""
    if op == "read":
        key_fids, key_ids, same = keys["fids"], keys["ids"], keys["same"]
        order = np.random.default_rng(
            [spec["seed"], 3, client_no]).integers(0, len(key_ids),
                                                   len(done))
        url = keys["url"]
    n = 0
    try:
        # Connections are dialled before the first timed request.
        http.request(http.master, "GET", "/dir/status")
        while n < count:
            t0 = time.monotonic()
            if t0 >= stop_at:
                break
            try:
                if op == "write":
                    ident = (client_no << 32) | n
                    fid, url = http.write(
                        coll, request_payload(block, ident, size))
                    fids[n] = fid.encode()
                    state = GOOD
                else:
                    k = order[n]
                    ident = key_ids[k]
                    state = GOOD if same(ident, http.read(
                        url, key_fids[k])) else DIFFERS
            except Exception:  # noqa: BLE001 - counted, the loop goes on
                state, ident = FAILED, -1
            t1 = time.monotonic()
            done[n], lat[n], ok[n], ids[n] = t1, t1 - t0, state, ident
            n += 1
    finally:
        arrays["n"][thread] = n
        arrays["url"][thread] = url if op == "write" else ""
        http.close()


def digest(payload: bytes) -> bytes:
    return hashlib.blake2b(payload, digest_size=16).digest()


def read_keys(spec: dict, block: bytes) -> dict:
    """A read mix's key set as a client uses it: `fids` and `ids` by
    key, the server's `url`, and `same(ident, answer)`."""
    with np.load(spec["keys"]) as z:
        keys = {k: z[k] for k in z.files}
    if spec.get("keys_from") != "pool":
        size = spec["size"]
        return {"fids": [f.decode() for f in keys["fids"]],
                "ids": [int(i) for i in keys["ids"]],
                "url": str(keys["url"]),
                "same": lambda ident, got: got == request_payload(
                    block, ident, size)}
    fids = [f.decode() for f in keys["fids"]]
    want = [(int(n), digest(needle_payload(spec["seed"], spec["stream"],
                                           int(i), int(n))))
            for i, n in zip(keys["idx"], keys["sizes"])]
    return {"fids": [f"{int(vid)},{f}" for vid in keys["vids"]
                     for f in fids],
            "ids": list(range(len(keys["vids"]) * len(fids))),
            "url": str(keys["url"]),
            "same": lambda ident, got: (len(got), digest(got))
            == want[ident % len(want)]}


def worker(spec: dict) -> None:
    threads = spec["threads"]
    cap = spec.get("count") or int(
        (spec["lead"] + spec["seconds"] + 5) * spec["cap_per_s"])
    arrays = {"done": np.zeros((threads, cap)),
              "lat": np.zeros((threads, cap)),
              "ok": np.zeros((threads, cap), np.int8),
              "ids": np.zeros((threads, cap), np.int64),
              "fids": np.zeros((threads, cap), f"S{FID_BYTES}"),
              "n": np.zeros(threads, np.int64), "url": [""] * threads}
    block = payload_block(spec["seed"])
    keys = read_keys(spec, block) if spec["op"] == "read" else None
    print("ready", flush=True)
    t_open = float(sys.stdin.readline())
    t_close = float("inf") if spec.get("count") \
        else t_open + spec["seconds"]
    ts = [threading.Thread(target=_client, args=(spec, t, block, keys,
                                                 arrays, t_close))
          for t in range(threads)]
    for t in ts:
        t.start()
    cpu = [0.0, 0.0]
    if not spec.get("count"):
        time.sleep(max(0.0, t_open - time.monotonic()))
        cpu[0] = time.process_time()
        time.sleep(max(0.0, t_close - time.monotonic()))
        cpu[1] = time.process_time()
    for t in ts:
        t.join()
    urls = {u for u in arrays.pop("url") if u}
    np.savez(spec["out"], cpu=np.array(cpu),
             url=np.array(urls.pop() if len(urls) == 1 else ""), **arrays)


def run(spec: dict, work: str, tag: str, at_open=None) -> dict:
    """Start `procs` workers on `spec`, wait until each is ready, open
    the window `lead` seconds later (the traffic before it warms the
    path), wait for them, and gather what every client recorded, flat:
    done, lat, ok, ids, fids, who, and url, cpu_s, t_open.  `at_open`
    runs in this process when the window opens."""
    procs = []
    outs = []
    try:
        for w in range(spec["procs"]):
            out = os.path.join(work, f"{tag}_{w}")
            path = out + ".json"
            with open(path, "w") as f:
                json.dump(dict(spec, worker=w, out=out), f)
            outs.append(out + ".npz")
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"), path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True))
        for p in procs:
            check(p.stdout.readline().strip() == "ready",
                  "a generator process did not come up")
        t_open = time.monotonic() + spec.get("lead", 0.0)
        for p in procs:
            p.stdin.write(f"{t_open!r}\n")
            p.stdin.flush()
        if at_open is not None:
            time.sleep(max(0.0, t_open - time.monotonic()))
            at_open()
        for p in procs:
            check(p.wait() == 0, f"a generator process exited "
                                 f"with {p.returncode}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
            p.stdin.close()
            p.stdout.close()
    got = {k: [] for k in ("done", "lat", "ok", "ids", "fids", "who")}
    cpu, urls = 0.0, set()
    for w, path in enumerate(outs):
        with np.load(path) as z:
            for t, n in enumerate(z["n"]):
                for k in got:
                    got[k].append(
                        np.full(n, w * spec["threads"] + t) if k == "who"
                        else z[k][t, :n])
            cpu += float(z["cpu"][1] - z["cpu"][0])
            if str(z["url"]):
                urls.add(str(z["url"]))
    flat = {k: np.concatenate(v) for k, v in got.items()}
    check(len(urls) <= 1, f"writes went to several servers: {urls}")
    flat["url"] = urls.pop() if urls else ""
    flat["cpu_s"] = cpu
    flat["t_open"] = t_open
    return flat


def in_window(res: dict, t_open: float, t_close: float,
              cores: int) -> dict:
    """The window's numbers from what the clients recorded: requests
    answered inside it, a failed one counting as slower than any."""
    seconds = t_close - t_open
    inside = (res["done"] >= t_open) & (res["done"] < t_close)
    ok = res["ok"][inside] == GOOD
    lat_ms = np.where(ok, res["lat"][inside] * 1e3, np.inf)
    n = int(inside.sum())
    check(n > 0, "no request was answered inside the window")
    lat_ms.sort()

    def pct(p: float) -> float:
        return float(lat_ms[min(n - 1, int(n * p))])

    per_client = np.bincount(res["who"][inside])
    answered = np.sort(res["done"][inside])
    stall = np.diff(answered, prepend=t_open, append=t_close).max()
    return {"attempted": n, "failed": int(n - ok.sum()),
            "longest_stall_ms": float(stall) * 1e3,
            "clients_active": int((per_client > 0).sum()),
            "least_client_share": float(per_client.min() * len(per_client)
                                        / n),
            "req_per_s": float(ok.sum()) / seconds,
            "p50_ms": pct(0.50), "p95_ms": pct(0.95), "p99_ms": pct(0.99),
            "client_cpu_share": 100.0 * res["cpu_s"] / (seconds * cores),
            "inside": inside}


if __name__ == "__main__":
    with open(sys.argv[1]) as _f:
        worker(json.load(_f))
