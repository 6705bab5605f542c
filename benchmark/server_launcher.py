"""Starts the program's own command entry — what `python -m seaweedfs_tpu
<role> ...` runs — with one thread beside it that the harness talks to
over a pair of pipes.  Only the process that owns the chip can trace it
or read its memory, and the program has no switch for either, so this is
where `jax.profiler` starts and stops around the window and where the
device's peak memory is read.  The thread sleeps in a pipe read unless
spoken to; it imports JAX only when asked, which is after the role has.

    server_launcher.py <fd to read commands> <fd to write replies> <role> [flags]

Commands, one line each, with one JSON line in reply:
    trace-start <dir>     jax.profiler.start_trace, host TraceMe spans on,
                          the Python tracer off
    trace-stop
    memory                memory_stats() of every local device
"""

from __future__ import annotations

import json
import os
import sys
import threading


def _serve(rfd: int, wfd: int) -> None:
    with os.fdopen(rfd) as commands, os.fdopen(wfd, "w") as replies:
        for line in commands:
            words = line.split()
            try:
                import jax
                if words[0] == "trace-start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(words[1],
                                             profiler_options=opts)
                    out = {"ok": True}
                elif words[0] == "trace-stop":
                    jax.profiler.stop_trace()
                    out = {"ok": True}
                elif words[0] == "memory":
                    out = {"ok": True, "devices": [
                        {"id": d.id, **{k: v for k, v in
                                        (d.memory_stats() or {}).items()
                                        if k in ("peak_bytes_in_use",
                                                 "bytes_in_use",
                                                 "bytes_limit")}}
                        for d in jax.local_devices()]}
                else:
                    out = {"ok": False, "error": f"unknown: {line!r}"}
            except Exception as e:  # noqa: BLE001 - reported to the harness
                out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            replies.write(json.dumps(out) + "\n")
            replies.flush()


def main(argv: list[str]) -> int:
    rfd, wfd = int(argv[0]), int(argv[1])
    threading.Thread(target=_serve, args=(rfd, wfd), daemon=True,
                     name="bench-control").start()
    from seaweedfs_tpu.command import main as program_main
    return program_main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
