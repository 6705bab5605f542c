"""EC file pipeline, under request load: what one (10, 4 MiB) chunk
costs the rebuild's main thread — the wait for the reader, the coder
call, the D2H, the CRC and the write (stage rows) over the chunks
dispatched.

`moves` is nominal: these seconds are the repair's, and what they move
is `rebuild_MBps` — which the cell reads under `seen` and cannot list
while one run in six to ten is in the process's second mode (the job
half again as fast, the clients a tenth slower: PERF.md, PR 32; ROADMAP
A13).  `req_per_s` is the one rate the cell lists, so the entry names
it."""

OP = "ec.rebuild"
ROWS = ("rebuild.read", "rebuild.dispatch", "rebuild.drain",
        "rebuild.write")


def read(facts):
    jobs, rows = facts["jobs"], facts["coder_rows"]
    if not jobs or jobs["op"] != OP:
        return None
    chunks = rows.get("rebuild.dispatch", {}).get("count")
    if not chunks:
        return None
    return 1e3 * sum(rows[r]["seconds"] for r in ROWS if r in rows) / chunks
