"""EC file pipeline: the main thread blocked on the read-ahead queue (the
reader did not keep up), as a share of the window (stage rows:
seal.stack_wait)."""

from benchmark import stages

OP, ROWS = "ec.encode", ("seal.stack_wait",)


def read(facts):
    return stages.share(facts, OP, ROWS)
