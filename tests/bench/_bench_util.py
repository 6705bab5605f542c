"""Shared by the benchmark's rehearsal tests: run one of its commands
at a tiny size on the CPU platform and hand back what it printed."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rehearse(script: str, workload: str, seed: int, trace: int = 0,
             root: str = ROOT, seconds: float = 2, flags=("--rehearse-cpu",),
             env: dict | None = None, before=()):
    """(exit code, stdout, stderr) of `<root>/benchmark/<script>` — or
    of a script of this directory, given by its path, with `before` and
    `--` ahead of the arguments.  The run and all it starts are niced:
    the suite's timing tests share these cores."""
    path = script if os.path.isabs(script) else \
        os.path.join(root, "benchmark", script)
    p = subprocess.run(
        [sys.executable, path, *before, "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace),
         *flags],
        capture_output=True, text=True, timeout=900, cwd=root,
        env=dict(os.environ, **(env or {})),
        preexec_fn=lambda: os.nice(15))
    return p.returncode, p.stdout, p.stderr


def rehearsal_result(stdout: str) -> dict:
    """The JSON a rehearsal prints after `rehearsal: platform=cpu ...`."""
    line = [ln for ln in stdout.splitlines()
            if ln.startswith("rehearsal: ")][-1]
    return json.loads(line[line.index("{"):])


def has_result_line(stdout: str) -> bool:
    """Whether any line is a result line the driver would read."""
    for ln in stdout.splitlines():
        if ln.startswith("{"):
            try:
                if "correct" in json.loads(ln):
                    return True
            except ValueError:
                pass
    return False
