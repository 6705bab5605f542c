#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the system's normal `server` role (master and volume server in
one process: the chip's one owner), drives it over HTTP and the admin
shell from the client's side, compares what the window produced with
the plain reference, and prints one JSON line last.  This launcher never
imports JAX.  No TPU, or a server that resolved another coder than the
Pallas one on a TPU: another exit code than 0 and no result line.

`--rehearse-cpu` runs the same path at a tiny size on the CPU platform
(Pallas in interpret mode) to debug where there is no chip: it prints
`platform=cpu` and never a result line.

What a cell does comes from data: its configuration, its traffic mix
(`jobs`: admin jobs on erasure-coded volumes, one after another;
`requests`: closed-loop clients; a mix may have both) and the readers of
its per-layer metrics.  See manifest.py.  A configuration may say
`server_env`: glibc's `MALLOC_*` variables for the server's process and
nothing else (served.py).  A traffic file may hold:

  jobs.op           `ec.encode` | `ec.rebuild`: the shell command, and the
                    state of the pool when the window opens (full quiet
                    volumes; sealed volumes without the configuration's
                    `lost_shards`)
  jobs.repeat       how many jobs the window runs, one after another,
                    over the pool's first volumes; or `jobs.per_second`,
                    as many for each second the run is given.  `repeat`
                    0 (with `requests`): the pool is made, damaged and
                    warmed, and no job runs in the window
  jobs.volumes      the volumes of the pool (default: one for each job)
  jobs.metric       the end-to-end metric the jobs' rate goes under
  jobs.volume_bytes, ec   sizes of the pool's volumes where they are not
                    the configuration's
  requests.op       `write` | `read`; requests.warm_seconds: the same
                    traffic for so long before the window opens
  requests.keys     a read mix's own key set, written in set-up into
                    unsealed volumes of collection `bench`; or
  requests.keys_from "pool"   reads only: the key set is every needle of
                    every volume of the jobs' pool, read from the volume
                    server and held against the seed's bytes
                    (loadgen.py); nothing is written or grown for it,
                    and of the configuration it asks `clients` and
                    `procs` beside what the jobs ask
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import (ecjobs, ecref, loadgen, manifest,  # noqa: E402
                       tracing)
from benchmark.data import Http, payload_block, request_payload  # noqa: E402
from benchmark.machine import (MIB, BenchFailure, check,  # noqa: E402
                               place_work_dir)
from benchmark.served import Server, call  # noqa: E402

CHUNK_BYTES = ecjobs.CHUNK_ROWS * ecjobs.ROW_BYTES     # 40 MiB of volume
FILE_SLACK = 16 * MIB          # a volume file past its nominal bytes
REHEARSAL_NEEDLE_MAX = MIB
WRITES_READ_BACK = 400
ROWS_SAMPLED = 4
NEEDLES_SAMPLED = 4
VOLUMES_COMPARED = 2
FIRST_CLONE_VID = 101


class Hooks:
    """Where a test or the control reaches into a run."""

    def before_window(self, ctx: dict) -> None:
        pass

    def after_window(self, ctx: dict) -> None:
        pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def setup_jobs(ctx: dict) -> None:
    """The volumes the window's jobs need, and every shape warm."""
    srv, seed, jobs, cfg = ctx["srv"], ctx["seed"], ctx["jobs"], ctx["ec"]
    needle_range = tuple(cfg["needle_bytes"])
    if ctx["rehearse"]:
        needle_range = (needle_range[0], REHEARSAL_NEEDLE_MAX)
    op, lost = jobs["op"], cfg["lost_shards"]
    # One small volume through the whole of the job: its coder calls
    # have the one shape every later call has.
    warm = ecjobs.fill_volume(srv, seed, 20, "warm", CHUNK_BYTES,
                              needle_range)
    ecjobs.run_job(srv, "ec.encode", warm.vid)
    if op == "ec.rebuild":
        ecjobs.lose(srv, [warm.vid], lost)
        ecjobs.run_job(srv, op, warm.vid)
    tpl = ecjobs.fill_volume(srv, seed, 21, "pool", ctx["volume_bytes"],
                             needle_range)
    vids = list(range(FIRST_CLONE_VID, FIRST_CLONE_VID + ctx["pool"]))
    if op == "ec.rebuild":
        ecjobs.run_job(srv, "ec.encode", tpl.vid)
        ecjobs.keep_shards(srv, tpl)
        ecjobs.clone_sealed(srv, tpl, vids, lost)
    else:
        ecjobs.clone_unsealed(srv, tpl, vids)
    ctx.update(template=tpl, vids=vids)


def pool_lost_shards(ctx: dict) -> list[int]:
    """The shards every volume of the pool is without when the window
    opens."""
    return ctx["ec"]["lost_shards"] if ctx["jobs"]["op"] == "ec.rebuild" \
        else []


def pool_keys(ctx: dict) -> str:
    """The key set of a `keys_from: "pool"` mix, as a file: every needle
    of every volume of the pool.  What the harness knows of each key
    stays here as `ctx["key_facts"]`: its payload's bytes, and the bytes
    of its record on a lost data shard, by key number."""
    srv, tpl, vids = ctx["srv"], ctx["template"], ctx["vids"]
    lost = pool_lost_shards(ctx)
    sizes = np.array([size for _f, _i, size in tpl.needles])
    gone = np.array([ecref.lost_bytes(offset, n, lost)
                     for offset, n in ecjobs.needle_records(tpl)])
    ctx["key_facts"] = {"bytes": np.tile(sizes, len(vids)),
                        "lost_bytes": np.tile(gone, len(vids))}
    path = os.path.join(srv.work, "keys.npz")
    np.savez(path, vids=np.array(vids), sizes=sizes,
             fids=np.array([f.encode() for f, _i, _s in tpl.needles]),
             idx=np.array([i for _f, i, _s in tpl.needles]),
             url=np.array(f"127.0.0.1:{srv.vport}"))
    return path


def setup_requests(ctx: dict) -> None:
    """Volumes grown before the window, and for reads the key set:
    written with the generator itself, or the pool's needles."""
    srv, req, cfg = ctx["srv"], ctx["requests"], ctx["store"]
    ctx["gen"] = {"master": srv.master, "op": req["op"],
                  "procs": cfg["procs"],
                  "threads": cfg["clients"] // cfg["procs"],
                  "seed": ctx["seed"], "cap_per_s": 4000}
    if ctx["pool_keys"]:
        ctx["gen"].update(size=0, collection=ctx["template"].collection,
                          keys_from="pool", keys=pool_keys(ctx),
                          stream=ctx["template"].stream)
        return
    call(f"{srv.master}/vol/grow?count={cfg['volumes']}"
         f"&collection=bench&replication={cfg['replication']}", {})
    ctx["gen"].update(size=cfg["size"], collection="bench")
    if req["op"] == "read":
        keys = ctx["keys"]
        per = -(-keys // cfg["clients"])
        res = loadgen.run(dict(ctx["gen"], op="write", count=per),
                          srv.work, "keys")
        check((res["ok"] == loadgen.GOOD).all(),
              "writing the key set: "
              f"{int((res['ok'] != loadgen.GOOD).sum())} writes failed")
        path = os.path.join(srv.work, "keys.npz")
        np.savez(path, ids=res["ids"], fids=res["fids"],
                 url=np.array(res["url"]))
        ctx["gen"]["keys"] = path


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def window(ctx: dict) -> None:
    srv, seconds = ctx["srv"], ctx["seconds"]
    jobs, req = ctx.get("jobs"), ctx.get("requests")
    vids = ctx["vids"][:ctx["window_jobs"]] if jobs else []
    marks: dict = {}

    def mark() -> None:
        # Every mark is taken as the window opens, the warm traffic
        # behind: the server's clock, its log and its rows (one GET of
        # `/debug/device`, which compiles and transfers nothing).
        marks.update(cpu=srv.cpu_seconds(), log=srv.log_mark(),
                     rows=srv.coder_rows())
    if ctx["trace"]:
        ctx["trace_dir"] = os.path.join(srv.work, "trace")
        srv.control(f"trace-start {ctx['trace_dir']}")
    t_traced = time.monotonic()
    if req:
        spec = dict(ctx["gen"], lead=req["warm_seconds"], seconds=seconds)
        done = {}

        def at_open() -> None:
            mark()
            if vids:        # the jobs run among the requests
                done.update(ecjobs.jobs_window(
                    srv, jobs["op"], vids, ctx["volume_bytes"]))
        res = loadgen.run(spec, srv.work, "window", at_open)
        t_open = res["t_open"]
        ctx["setup_s"] = t_open - T_START
        ctx["window_s"] = seconds
        ctx["res"] = res
        ctx["req"] = loadgen.in_window(res, t_open, t_open + seconds,
                                       os.cpu_count() or 1)
        ctx["req"]["op"] = req["op"]
        if ctx["pool_keys"]:
            ctx["req"].update(pool_facts(ctx))
        ctx["job"] = done
    else:
        mark()
        ctx["setup_s"] = time.monotonic() - T_START
        ctx["job"] = ecjobs.jobs_window(srv, jobs["op"], vids,
                                        ctx["volume_bytes"])
        ctx["window_s"] = ctx["job"]["window_s"]
    if ctx["trace"]:
        ctx["traced_s"] = time.monotonic() - t_traced
        srv.control("trace-stop")
    rows = srv.coder_rows()
    ctx["coder_rows"] = {
        k: {f: v[f] - marks["rows"].get(k, {}).get(f, 0) for f in v}
        for k, v in rows.items()}
    ctx["server_cpu_s"] = srv.cpu_seconds() - marks["cpu"]
    ctx["compiles"] = srv.compiles(marks["log"], srv.log_mark())


def pool_facts(ctx: dict) -> dict:
    """Of the window's answered reads of pool keys: how many, their
    payload bytes, the bytes of their records that lie on a lost data
    shard (what any implementation HAS to reconstruct to answer them:
    the harness's own layout, never a counter of the program's), and
    how many had any."""
    res, facts = ctx["res"], ctx["key_facts"]
    keys = res["ids"][ctx["req"]["inside"] & (res["ok"] != loadgen.FAILED)]
    gone = facts["lost_bytes"][keys]
    return {"pool_reads": len(keys),
            "pool_read_bytes": int(facts["bytes"][keys].sum()),
            "pool_lost_bytes": int(gone.sum()),
            "pool_reads_on_lost_shards": int((gone > 0).sum())}


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------

def compare(ctx: dict) -> dict:
    """Every number compared, each with the limit 0 (the comparisons are
    exact): {name: [value, limit]}."""
    srv, seed = ctx["srv"], ctx["seed"]
    out: dict = {}
    if ctx.get("job"):
        tpl, op = ctx["template"], ctx["jobs"]["op"]
        lost = ctx["ec"]["lost_shards"]
        done = [vid for vid, _t0, _t1 in ctx["job"]["jobs"]]
        # Every volume of the window, or the last and others drawn
        # from the seed: the comparison stays shorter than the window.
        if len(done) > VOLUMES_COMPARED:
            more = np.random.default_rng([seed, 76]).choice(
                done[:-1], VOLUMES_COMPARED - 1, replace=False)
            picked = sorted({done[-1], *(int(v) for v in more)})
            out["files_missing"] = sum(      # the cheap look, at the rest
                ecjobs.files_missing(os.path.join(
                    srv.data_dir, f"{tpl.collection}_{vid}"), tpl)
                for vid in done if vid not in picked)
            done = picked
        out["volumes_compared"] = len(done)
        for vid in done:
            base = os.path.join(srv.data_dir, f"{tpl.collection}_{vid}")
            if op == "ec.rebuild":
                part = ecjobs.compare_shards(base, tpl, seed, vid,
                                             ROWS_SAMPLED, shards=lost)
                if not part["files_missing"]:
                    part["rebuilt_files_differ"] = sum(
                        ecjobs.files_differ(
                            base + ecref.ext(sid),
                            os.path.join(tpl.shard_dir,
                                         "shard" + ecref.ext(sid)))
                        for sid in lost)
            else:
                part = ecjobs.compare_shards(base, tpl, seed, vid,
                                             ROWS_SAMPLED)
                # deleted only once the shards are mounted: it is gone,
                # and the needles below come from the shards
                part["originals_left"] = int(os.path.exists(base + ".dat"))
            part.update(ecjobs.compare_needles(srv, tpl, seed, vid,
                                               NEEDLES_SAMPLED))
            ecjobs.add(out, part)
    if ctx.get("req"):
        res, inside = ctx["res"], ctx["req"]["inside"]
        out["requests_failed"] = int(
            (res["ok"][inside] == loadgen.FAILED).sum())
        out["answers_differ"] = int(
            (res["ok"][inside] == loadgen.DIFFERS).sum())
        if ctx["pool_keys"]:
            # A run whose draw never touched a lost shard says so; and
            # a read repairs nothing on disk: no shard the configuration
            # lost is back in a volume that no job of the window rebuilt.
            out["pool_reads_on_lost_shards"] = \
                ctx["req"]["pool_reads_on_lost_shards"]
            tpl = ctx["template"]
            rebuilt = {vid for vid, _t0, _t1 in
                       (ctx.get("job") or {}).get("jobs", ())}
            out["lost_shards_back"] = sum(
                os.path.exists(os.path.join(
                    srv.data_dir, f"{tpl.collection}_{vid}") + ecref.ext(sid))
                for vid in ctx["vids"] if vid not in rebuilt
                for sid in pool_lost_shards(ctx))
        if ctx["req"]["op"] == "write":
            # Every acknowledged write reads back byte for byte: a
            # sample of the window's, drawn from the seed.
            idx = np.flatnonzero(inside & (res["ok"] == loadgen.GOOD))
            pick = np.random.default_rng([seed, 79]).choice(
                idx, min(WRITES_READ_BACK, len(idx)), replace=False)
            block, http, bad = payload_block(seed), Http(srv.master), 0
            try:
                for j in pick:
                    try:
                        got = http.read(res["url"],
                                        res["fids"][j].decode())
                    except (BenchFailure, OSError):
                        got = None
                    bad += got != request_payload(
                        block, int(res["ids"][j]), ctx["store"]["size"])
            finally:
                http.close()
            out["writes_read_back"] = len(pick)
            out["writes_lost_or_differ"] = bad
    counted = ("needles_read", "writes_read_back", "volumes_compared",
               "pool_reads_on_lost_shards")
    return {k: [v, None if k in counted else 0] for k, v in out.items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def reduce_trace(ctx: dict) -> dict | None:
    """The trace read in a child on the CPU platform: the launcher
    stays off JAX, and the server, which held the chip, has gone."""
    if not ctx["trace"]:
        return None
    out = os.path.join(ctx["srv"].work, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, os.path.join(HERE, "tracing.py"),
                    ctx["trace_dir"], out], check=True, env=env,
                   timeout=200)
    with open(out) as f:
        return json.load(f)


def measures(ctx: dict) -> dict:
    """Every end-to-end quantity this run can report, by metric name."""
    out = {"setup_s": ctx["setup_s"]}
    jobs = ctx.get("jobs")
    if ctx.get("job") and jobs.get("metric"):
        out[jobs["metric"]] = ctx["job"]["MBps"]
    if ctx.get("req"):
        out["req_per_s"] = ctx["req"]["req_per_s"]
        out["req_p95_ms"] = ctx["req"]["p95_ms"]
        for extra in ("clients_active", "least_client_share", "pool_reads",
                      "pool_read_bytes", "pool_lost_bytes"):
            if extra in ctx["req"]:
                out[extra] = ctx["req"][extra]
    return out


def facts_of(ctx: dict, trace: dict | None) -> dict:
    """What a per-layer reader may read."""
    job = ctx.get("job") or {}
    req = {k: v for k, v in (ctx.get("req") or {}).items()
           if k != "inside"}
    return {"cell": ctx["cell"]["name"], "window_s": ctx["window_s"],
            "device_kind": ctx["device"]["kind"],
            "jobs": {"op": ctx["jobs"]["op"], "count": len(job["jobs"]),
                     "shard_bytes": ecref.shard_size(
                         ctx["template"].dat_bytes),
                     "lost": len(ctx["ec"]["lost_shards"])}
            if job else None,
            "requests": req or None,
            "server_cpu_s": ctx["server_cpu_s"],
            "compiles": ctx["compiles"],
            "coder_rows": ctx["coder_rows"],
            "traced_s": ctx.get("traced_s"), "trace": trace}


def run(args, hooks: Hooks) -> dict:
    """One run; the result as printed (a rehearsal prints it as a
    rehearsal's, never as a result line)."""
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    traffic, cfg = cell["traffic"], cell["config"]
    rehearse = args.rehearse_cpu
    ctx: dict = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
                 "trace": bool(args.trace), "rehearse": rehearse,
                 "jobs": traffic.get("jobs"),
                 "requests": traffic.get("requests")}
    check(ctx["jobs"] or ctx["requests"],
          f"traffic {cell['traffic_name']!r} has neither jobs nor requests")
    ctx["pool_keys"] = (ctx["requests"] or {}).get("keys_from") == "pool"
    check(not ctx["pool_keys"]
          or ctx["jobs"] and ctx["requests"]["op"] == "read",
          f"traffic {cell['traffic_name']!r}: `keys_from: \"pool\"` is "
          f"for reads, of a pool that `jobs` make")
    ctx["ec"] = traffic.get("ec") or cfg      # sizes of the jobs' volumes
    ctx["store"] = cfg                        # shape of the requests
    volume_max = 16
    if ctx["jobs"]:
        jobs = ctx["jobs"]
        want = CHUNK_BYTES if rehearse else \
            jobs.get("volume_bytes") or ctx["ec"]["volume_bytes"]
        work, machine = place_work_dir(want + FILE_SLACK)
        fits = (machine["file_cap"] - FILE_SLACK) // CHUNK_BYTES \
            * CHUNK_BYTES
        ctx["volume_bytes"] = min(want, fits)
        check(ctx["volume_bytes"] >= CHUNK_BYTES,
              f"a file here may hold {machine['file_cap']} bytes: too "
              f"small for a volume ({machine})")
        # A fixed amount of work: so many jobs, or so many for each
        # second the run was given, over a pool of as many volumes or
        # of as many as the mix says.
        ctx["window_jobs"] = jobs["repeat"] if "repeat" in jobs else (
            2 if rehearse else
            max(1, round(args.seconds * jobs["per_second"])))
        ctx["pool"] = jobs.get("volumes", ctx["window_jobs"])
        check(ctx["pool"] >= max(1, ctx["window_jobs"])
              and (ctx["window_jobs"] or ctx["requests"]),
              f"traffic {cell['traffic_name']!r}: {ctx['window_jobs']} "
              f"jobs over {ctx['pool']} volumes")
        # What a run writes: the filled volume, and per job 14 shards
        # of a tenth of the volume each, or the lost ones again.
        written = 1.4 if jobs["op"] == "ec.encode" else \
            2.4 / ctx["pool"] + len(ctx["ec"]["lost_shards"]) / 10
        need = int((1 + ctx["pool"] * written) * ctx["volume_bytes"]) \
            + 512 * MIB
        check(machine["free_bytes"] >= need,
              f"{need} bytes are needed under {machine['work_parent']} "
              f"and {machine['free_bytes']} are free")
        volume_max += ctx["pool"]
        ctx["machine"] = machine
    else:
        work, ctx["machine"] = place_work_dir(FILE_SLACK)
    if ctx["requests"] and not ctx["pool_keys"]:
        volume_max += ctx["store"]["volumes"]
        ctx["keys"] = 256 if rehearse else ctx["requests"].get("keys", 0)
    say(f"bench: {cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} machine={json.dumps(ctx['machine'])}")

    srv = Server(work, rehearse, volume_max, cfg.get("server_env"))
    ctx["srv"] = srv
    try:
        resolved = srv.wait_ready()
        ctx["device"] = {"platform": resolved["platform"],
                         "kind": resolved["kind"],
                         "count": resolved["count"]}
        say(f"bench: server up {time.monotonic() - T_START:.1f}s after "
            f"start, resolved {resolved}")
        check(resolved["coder"] == "pallas"
              and resolved["platform"] == ("cpu" if rehearse else "tpu")
              and resolved["count"] >= (1 if rehearse else cell["chips"]),
              f"the server resolved {resolved}; the cell wants the pallas "
              f"coder on {cell['chips']} TPU chip(s)")
        if ctx["jobs"]:
            setup_jobs(ctx)
        if ctx["requests"]:
            setup_requests(ctx)
        hooks.before_window(ctx)
        os.sync()       # set-up's dirty pages go out before the window
        window(ctx)
        if ctx.get("job"):
            say("bench: jobs took " + " ".join(
                f"{t1 - t0:.3f}" for _v, t0, t1 in ctx["job"]["jobs"]))
        ctx["device"]["memory_peak_bytes"] = srv.memory_peak_bytes()
        hooks.after_window(ctx)
        t0 = time.monotonic()
        compared = compare(ctx)
        ctx["compare_s"] = time.monotonic() - t0
    finally:
        srv.stop()
    try:
        check(srv.proc.returncode == 0,
              f"the server exited with {srv.proc.returncode}")
        trace = reduce_trace(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        os.sync()       # the next run finds the disk as this one did

    return report(ctx, man, trace, compared)


def report(ctx: dict, man: dict, trace: dict | None,
           compared: dict) -> dict:
    """The result line (a rehearsal's is marked as one), then each
    number compared beside its limit, last on standard error."""
    name, rehearse = ctx["cell"]["name"], ctx["rehearse"]
    got = measures(ctx)
    facts = facts_of(ctx, trace)
    layer = {}
    for m in manifest.metrics_of(man, name, "per_layer"):
        value = manifest.reader(man, m["name"])(facts)
        if value is not None:
            layer[m["name"]] = {"value": value, "unit": m["unit"]}
    if ctx["trace"]:
        metrics = layer
    else:
        metrics = {}
        for m in manifest.metrics_of(man, name, "end_to_end"):
            check(m["name"] in got, f"{name} does not measure "
                                    f"{m['name']}: it has {sorted(got)}")
            metrics[m["name"]] = {"value": got[m["name"]],
                                  "unit": m["unit"]}
    req, job = ctx.get("req"), ctx.get("job")
    correct = all(lim is None or v <= lim for v, lim in compared.values())
    result = {"correct": bool(correct),
              "attempted": req["attempted"] if req else len(job["jobs"]),
              "failed": req["failed"] if req else 0,
              "metrics": metrics, "device": ctx["device"]}
    if trace is not None:
        check(trace["busy_s"] > 0 or rehearse,
              "no operation ran on the device in the traced window")
        result["device"].update(busy_s=trace["busy_s"],
                                window_s=ctx["traced_s"])
        result["breakdown"] = {"device_ops": tracing.top_ops(trace["ops"]),
                               "idle_gaps": trace["gaps"]}
    # Beside the metrics asked for, what else this run read (the driver
    # ignores it; the builder's notes do not).
    result["seen"] = {k: v for k, v in got.items() if k not in metrics}
    result["seen"].update({k: v["value"] for k, v in layer.items()
                           if k not in metrics})
    result["seconds"] = {"window": ctx["window_s"],
                         "compare": ctx["compare_s"]}
    result["compared"] = compared
    if rehearse:
        say(f"rehearsal: platform={ctx['device']['platform']} "
            f"correct={correct} {json.dumps(result)}")
    else:
        say(json.dumps(result))
    print("\n".join(f"compared {k}: {v} (limit {lim})"
                    for k, (v, lim) in compared.items()),
          file=sys.stderr, flush=True)
    return result


def parse(argv: list[str]):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU platform, Pallas in "
                         "interpret mode; never a result line")
    return ap.parse_args(argv)


def main(argv: list[str], hooks: Hooks | None = None) -> int:
    """0 for a run that printed its result (a rehearsal: and was
    correct)."""
    args = parse(argv)
    try:
        result = run(args, hooks or Hooks())
    except BenchFailure as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    return 0 if result["correct"] or not args.rehearse_cpu else 1


if __name__ == "__main__":
    # A terminated launcher still stops what it started.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    _rc = main(sys.argv[1:])
    assert "jax" not in sys.modules, "the launcher imported JAX"
    sys.exit(_rc)
