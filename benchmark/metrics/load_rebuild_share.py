"""EC file pipeline, under request load: how much of the window a
rebuild ran — the rebuild's main-thread stage rows (every `rebuild.`
row; `beside.rebuild_read`, the read-ahead thread beside it, is outside
by its name) over the window.

`moves` is nominal: these seconds are the repair's, and what they move
is `rebuild_MBps` — which the cell reads under `seen` and cannot list
while one run in six to ten is in the process's second mode (the job
half again as fast, the clients a tenth slower: PERF.md, PR 32; ROADMAP
A13).  `req_per_s` is the one rate the cell lists, so the entry names
it."""

from benchmark import stages

OP = "ec.rebuild"


def read(facts):
    rows = [r for r in facts["coder_rows"] if r.startswith("rebuild.")]
    return stages.share(facts, OP, rows)
