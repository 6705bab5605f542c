"""The system under test as the harness holds it: one `server` process
(master and volume server: the chip's one owner), its log, its counters
and its admin shell, all from the client's side.  Never imports JAX.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from .machine import BenchFailure, check

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_COMPILE = re.compile(r"Finished XLA compilation of jit\((.+?)\) in "
                      r"([0-9.eE+-]+) sec")
_DEVICE = re.compile(r"(\S+) device: coder=(\S+) platform=(\S+) "
                     r"device_kind='([^']*)' devices=(\d+)")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def call(url: str, body: dict | None = None, timeout: float = 300.0):
    """GET, or POST of a JSON body; the JSON answer."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if body is None else "POST",
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw = r.read()
    except urllib.error.HTTPError as e:
        raise BenchFailure(f"{url}: HTTP {e.code}: "
                           f"{e.read()[:300]!r}") from None
    return json.loads(raw) if raw else {}


def log_tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


ALLOCATOR_ENV = "MALLOC_"


def server_environment(rehearse: bool, allocator: dict | None,
                       base: dict | None = None) -> dict:
    """The environment the server starts in: the harness's own less
    every SEAWEEDFS_TPU_* variable (defaults are what is under test),
    every program into the persistent cache, and what the
    configuration's `server_env` says of the C library's allocator —
    glibc's `MALLOC_*` variables and nothing else: a configuration
    cannot reach a switch of the program or of JAX this way.  glibc
    adjusts its `mmap` and trim thresholds to the sizes a process has
    freed, so whether a later block comes from warm heap or from fresh
    pages depends on the set-up's history: the same program then runs
    in one of two modes from its first job to its last (PERF.md, PR 34),
    and a deployment that fixes them runs in one."""
    env = {k: v for k, v in (os.environ if base is None else base).items()
           if not k.startswith("SEAWEEDFS_TPU_")}
    # Every program goes to the persistent cache, the sub-second
    # kernels too: only a checkout's first run compiles.
    env.update(PYTHONPATH=ROOT, JAX_LOG_COMPILES="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    if rehearse:
        env.update(JAX_PLATFORMS="cpu", SEAWEEDFS_TPU_CODER="pallas",
                   SEAWEEDFS_TPU_EC_FUSED_CRC="1")
    for name, value in (allocator or {}).items():
        check(name.startswith(ALLOCATOR_ENV) and isinstance(value, str),
              f"server_env: {name!r}: only {ALLOCATOR_ENV}* variables, "
              f"as strings")
        env[name] = value
    return env


class Server:
    """`server_launcher.py server ...` in `work`, volumes under
    `work/data`.  `rehearse` asks for the chip's code path on the CPU
    platform (Pallas in interpret mode); `allocator` is the
    configuration's `server_env` (server_environment)."""

    def __init__(self, work: str, rehearse: bool, volume_max: int,
                 allocator: dict | None = None):
        self.work = work
        self.data_dir = os.path.join(work, "data")
        os.makedirs(self.data_dir, exist_ok=True)
        self.log_path = os.path.join(work, "server.log")
        self.mport, self.vport = free_port(), free_port()
        self.master = f"http://127.0.0.1:{self.mport}"
        self.volume = f"http://127.0.0.1:{self.vport}"
        env = server_environment(rehearse, allocator)
        to_child, self._cmd_w = os.pipe()
        self._reply_r, from_child = os.pipe()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "server_launcher.py"),
                 str(to_child), str(from_child), "server",
                 f"-dir={self.data_dir}", f"-mdir={work}",
                 f"-master.port={self.mport}", f"-volume.port={self.vport}",
                 f"-volume.max={volume_max}"],
                env=env, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
                pass_fds=(to_child, from_child))
        os.close(to_child)
        os.close(from_child)
        self._commands = os.fdopen(self._cmd_w, "w")
        self._replies = os.fdopen(self._reply_r)
        self._shell_env = None
        self.resolved: dict = {}

    # -- lifecycle -------------------------------------------------------

    def wait_ready(self, timeout: float = 240.0) -> dict:
        """The start-up device line, then a registered data node."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            check(self.proc.poll() is None,
                  f"server exited with {self.proc.returncode}:\n"
                  f"{log_tail(self.log_path)}")
            if not self.resolved:
                with open(self.log_path, errors="replace") as f:
                    m = _DEVICE.search(f.read())
                if m:
                    self.resolved = {
                        "coder": m.group(2), "platform": m.group(3),
                        "kind": m.group(4), "count": int(m.group(5))}
            else:
                try:
                    st = call(f"{self.master}/dir/status", timeout=2.0)
                    if st.get("topology", {}).get("children"):
                        return self.resolved
                except (BenchFailure, OSError):
                    pass
            time.sleep(0.1)
        raise BenchFailure(f"server not ready in {timeout:.0f}s:\n"
                           f"{log_tail(self.log_path)}")

    def stop(self) -> None:
        if self._shell_env is not None:
            self._shell_env.close()
            self._shell_env = None
        for f in (self._commands, self._replies):
            try:
                f.close()
            except OSError:
                pass
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=40)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)

    # -- the admin shell, from the client's side ---------------------------

    def shell(self, line: str) -> str:
        from seaweedfs_tpu.shell import CommandEnv, run_command
        if self._shell_env is None:
            self._shell_env = CommandEnv(self.master)
            run_command(self._shell_env, "lock")
        return run_command(self._shell_env, line)

    # -- counters ----------------------------------------------------------

    def control(self, line: str) -> dict:
        self._commands.write(line + "\n")
        self._commands.flush()
        reply = json.loads(self._replies.readline() or "{}")
        check(reply.get("ok"), f"server control {line!r}: {reply}")
        return reply

    def memory_peak_bytes(self) -> int:
        """The peak on the fullest chip (its bytes in use where the
        backend keeps no peak)."""
        peaks = [d.get("peak_bytes_in_use", d.get("bytes_in_use", 0)) or 0
                 for d in self.control("memory")["devices"]]
        return max(peaks, default=0)

    def cpu_seconds(self) -> float:
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    def log_mark(self) -> int:
        return os.path.getsize(self.log_path)

    def compiles(self, start: int = 0, end: int | None = None) -> dict:
        """XLA compilations the server logged between two log marks."""
        with open(self.log_path, "rb") as f:
            f.seek(start)
            text = f.read(None if end is None else end - start).decode(
                errors="replace")
        found = _COMPILE.findall(text)
        return {"count": len(found),
                "seconds": sum(float(s) for _n, s in found)}

    def coder_rows(self) -> dict[str, dict]:
        """`/debug/device` summed by row name: count, seconds, bytes.
        A kernel's row holds its fenced calls (the tail of the transfer
        in and the kernel; the copy back is outside the fence); a
        pipeline's unfenced calls leave none, and its stages' rows
        follow in the same list."""
        out: dict[str, dict] = {}
        for r in call(f"{self.volume}/debug/device")["kernels"]:
            row = out.setdefault(r["kernel"],
                                 {"count": 0, "seconds": 0.0, "bytes": 0})
            row["count"] += r["count"]
            row["seconds"] += r["seconds"]
            row["bytes"] += r["bytes"]
        return out
