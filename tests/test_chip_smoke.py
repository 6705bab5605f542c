"""chip_smoke.py's contract, as far as a CPU can check it: the
rehearsal (same command path, tiny sizes, Pallas in interpret mode)
passes and is never reported as a chip result, also on a machine that
caps the size of a file below one volume; without the flag and without
a TPU the script fails and prints no result line; alone in a directory
it fails."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, env=None, timeout=600, ulimit_f=None):
    # conftest pinned this process (and so the child) to the CPU
    # platform with 8 virtual devices.
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    argv = [sys.executable, *argv]
    if ulimit_f is not None:        # in KiB, as the shell counts it
        argv = ["bash", "-c", f'ulimit -f {ulimit_f} && exec "$@"', "--",
                *argv]
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_rehearsal_passes_and_is_not_a_chip_result():
    p = _run([SMOKE, "--rehearse-cpu"])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and "ok" not in last
    assert last["device"]["platform"] == "cpu"
    assert "served=pass batch=pass" in p.stdout
    assert '"ok"' not in p.stdout


def test_rehearsal_fits_a_machine_that_caps_file_size():
    """The chip check's machine refused a write past its file-size limit
    (EFBIG on upload): the smoke measures the cap and seals as many
    smaller volumes as hold the same bytes."""
    # 16 MiB: below the rehearsal's 24 MiB volume
    p = _run([SMOKE, "--rehearse-cpu"], ulimit_f=16 << 10)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "served=pass batch=pass" in p.stdout
    assert "2 served volumes for one" in p.stdout
    assert "File too large" not in p.stdout + p.stderr


def test_without_a_tpu_it_fails_and_prints_no_result():
    p = _run([SMOKE])
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "wanted the pallas coder on tpu" in p.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run(["chip_smoke.py"], cwd=tmp_path, env=env)
    assert p.returncode != 0 and '"ok"' not in p.stdout
