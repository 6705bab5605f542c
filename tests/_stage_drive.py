"""One seal and one rebuild of a small volume through the volume
server's own admin handlers, and a GET of its needle while a data
shard is gone and again when it is back, in process, for
tests/test_stage_clock.py.

`drive(tmp)` returns what the operator surfaces said afterwards.  Run as
a script, the same drive happens under `jax.profiler` on the CPU
platform, the `.xplane.pb` is read back with the benchmark's own
`tracing.load()`, and one JSON line says which host events carry a stage
name and for how long:

    python tests/_stage_drive.py <work dir>
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LOST = [3, 11]


def drive(tmp: str, payload_bytes: int = 3 << 20) -> dict:
    from seaweedfs_tpu.cluster import rpc
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.events.journal import JOURNAL
    from seaweedfs_tpu.trace import BUFFER

    master = MasterServer(volume_size_limit_mb=64, meta_dir=tmp,
                          pulse_seconds=60)
    master.start()
    vdir = os.path.join(tmp, "vs0")
    os.makedirs(vdir, exist_ok=True)
    vs = VolumeServer(master.url(), [vdir], pulse_seconds=60)
    vs.start()
    try:
        rpc.call(f"{master.url()}/vol/grow?count=1&collection=stage",
                 "POST")
        a = rpc.call(f"{master.url()}/dir/assign?collection=stage")
        rpc.call(f"http://{a['url']}/{a['fid']}", "POST",
                 os.urandom(payload_bytes))
        vid, url = int(a["fid"].split(",")[0]), f"http://{a['url']}"

        def admin(path: str, **body) -> dict:
            return rpc.call_json(f"{url}/admin/{path}", "POST",
                                 dict(body, volume=vid))

        # what `ec.encode` and `ec.rebuild` ask of the one holder
        admin("ec/generate")
        admin("ec/mount")
        admin("delete_volume")
        admin("ec/delete_shards", shards=LOST)
        # a GET of the needle while shard 3 is gone takes the degraded
        # read's third rung; after the rebuild the same GET is healthy
        degraded = bytes(rpc.call(f"{url}/{a['fid']}"))
        rebuilt = admin("ec/rebuild")["rebuilt_shards"]
        admin("ec/mount")
        assert bytes(rpc.call(f"{url}/{a['fid']}")) == degraded

        finish = {t: JOURNAL.snapshot(type_=t, limit=1)[0]
                  for t in ("ec.encode.finish", "ec.rebuild.finish")}
        spans = {t: [s for s in BUFFER.get(ev["trace_id"]) or []
                     if s["kind"] == "server"]
                 for t, ev in finish.items()}
        return {"vid": vid, "rebuilt": rebuilt, "finish": finish,
                "spans": spans,
                "device": rpc.call(f"{url}/debug/device")}
    finally:
        vs.stop()
        master.stop()


def main(work: str) -> int:
    from seaweedfs_tpu.utils.jaxenv import force_cpu
    force_cpu(1)
    import jax

    from benchmark import tracing
    from seaweedfs_tpu.stats import roofline

    trace_dir = os.path.join(work, "trace")
    opts = jax.profiler.ProfileOptions()     # as server_launcher.py's
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        got = drive(work)
    finally:
        jax.profiler.stop_trace()
    _device, host = tracing.load(tracing.newest_xplane(trace_dir))
    events: dict = {}
    for name, _start, seconds in host:
        if name in roofline.STAGES:
            ev = events.setdefault(name, [0, 0.0])
            ev[0] += 1
            ev[1] += seconds
    rows = {r["kernel"]: r for r in got["device"]["kernels"]
            if r["kernel"] in roofline.STAGES}
    print(json.dumps({"events": events, "rows": rows,
                      "host_events": len(host)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
