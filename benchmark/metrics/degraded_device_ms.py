"""Pallas coder: the device rung of a degraded read, per GET that
reached it — AND THE READER THAT FAILS A RUN (`bench: FAILED`, exit 1,
no result line, at `--trace 0` too) whose server rebuilt lost bytes
without the rung.  The number: the transfer's issue and the launch (row
`read.dispatch`) plus the copy back and the wait for it (row
`read.drain`), seconds over row `read.degraded`'s count.

Why it refuses: the cell measures the degraded read's device
rung, and its number is the number of that path.  Where the harness
counted answered reads on lost shards and the server left no
`read.dispatch` row, the lost bytes were rebuilt some other way (a
program from before the rung compiles per interval inside the GET, and
its tail is a queue on the compiler that no bound holds): the run fails
and prints no result line, as `run.py` does for a server that resolved
another coder than the Pallas one.  The gate hangs on a row NAME of the
program (`read.dispatch`, stats/roofline.py `STAGES`): a PR that renames
the row fails every run of this cell, which is the point and the cost."""

from benchmark.machine import check

ROWS, PER = ("read.dispatch", "read.drain"), "read.degraded"


def read(facts):
    req, rows = facts["requests"], facts["coder_rows"]
    if not req or not req.get("pool_reads_on_lost_shards"):
        return None
    launched = rows.get(ROWS[0])
    check(launched and launched["count"],
          f"the cell measures the degraded read's device rung; this "
          f"server rebuilt lost bytes some other way: "
          f"{req['pool_reads_on_lost_shards']} answered reads on lost "
          f"shards and no `{ROWS[0]}` row")
    per = rows.get(PER)
    if not per or not per["count"]:
        return None
    return 1e3 * sum(rows[r]["seconds"] for r in ROWS if r in rows) \
        / per["count"]
