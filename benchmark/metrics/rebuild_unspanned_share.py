"""EC file pipeline: the window less every main-thread `rebuild.*` stage
row: what no stage of the program covers (the shell in the harness,
HTTP, the master's lookups, heartbeats)."""

from benchmark import stages

OP, PREFIX = "ec.rebuild", "rebuild."


def read(facts):
    return stages.unspanned_share(facts, OP, PREFIX)
