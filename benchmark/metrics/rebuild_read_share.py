"""EC file pipeline: pread of the planned survivors, as a share of the window
(stage rows: rebuild.read)."""

from benchmark import stages

OP, ROWS = "ec.rebuild", ("rebuild.read",)


def read(facts):
    return stages.share(facts, OP, ROWS)
