"""EC file pipeline: the CRC accumulator's feed and the write of the rebuilt
shards, as a share of the window (stage rows: rebuild.write)."""

from benchmark import stages

OP, ROWS = "ec.rebuild", ("rebuild.write",)


def read(facts):
    return stages.share(facts, OP, ROWS)
