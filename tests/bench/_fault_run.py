"""One rehearsal with the timed path broken underneath.

    _fault_run.py flip <shard id> | nothing  -- <arguments of run.py>

`flip`: after every job of the window, one byte of that shard is
flipped where the job wrote it (an answer altered where it is produced).
`nothing`: the window's jobs return, after a while, without having done
anything (a step that leaves its state unchanged).  Set-up's jobs, on
volume ids under the pool's, run as they are."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import ecjobs, ecref, run  # noqa: E402

REAL = ecjobs.run_job


def flip(sid: int):
    def job(srv, op, vid):
        REAL(srv, op, vid)
        if vid >= run.FIRST_CLONE_VID:
            path = os.path.join(srv.data_dir, f"pool_{vid}{ecref.ext(sid)}")
            with open(path, "r+b") as f:
                f.seek(4097)
                byte = f.read(1)
                f.seek(4097)
                f.write(bytes([byte[0] ^ 0x40]))
    return job


def nothing(srv, op, vid):
    if vid < run.FIRST_CLONE_VID:
        REAL(srv, op, vid)
    else:
        time.sleep(0.3)


if __name__ == "__main__":
    cut = sys.argv.index("--")
    fault = sys.argv[1:cut]
    ecjobs.run_job = flip(int(fault[1])) if fault[0] == "flip" else nothing
    sys.exit(run.main(sys.argv[cut + 1:]))
