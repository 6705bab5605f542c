"""EC file pipeline: the read-ahead thread busy reading and stacking chunks,
beside the main thread, as a share of the window (stage rows: seal.stack)."""

from benchmark import stages

OP, ROWS = "ec.encode", ("seal.stack",)


def read(facts):
    return stages.share(facts, OP, ROWS)
