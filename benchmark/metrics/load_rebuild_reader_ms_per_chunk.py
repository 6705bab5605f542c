"""EC file pipeline, under request load: what one (10, 4 MiB) chunk
costs the rebuild's read-ahead thread — the preadv of every planned
survivor into a pooled buffer (row `beside.rebuild_read`: seconds over
count).  Over `load_rebuild_ms_per_chunk` and the reader sets the
pace.

`moves` is nominal: these seconds are the repair's, and what they move
is `rebuild_MBps` — which the cell reads under `seen` and cannot list
while one run in six to ten is in the process's second mode (the job
half again as fast, the clients a tenth slower: PERF.md, PR 32; ROADMAP
A13).  `req_per_s` is the one rate the cell lists, so the entry names
it."""

OP, ROW = "ec.rebuild", "beside.rebuild_read"


def read(facts):
    jobs = facts["jobs"]
    if not jobs or jobs["op"] != OP:
        return None
    row = facts["coder_rows"].get(ROW)
    return 1e3 * row["seconds"] / row["count"] if row and row["count"] \
        else None
