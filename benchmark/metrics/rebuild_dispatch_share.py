"""EC file pipeline: coder.reconstruct (ten transfers, the stack, the kernel,
the fence's wait), as a share of the window (stage rows: rebuild.dispatch)."""

from benchmark import stages

OP, ROWS = "ec.rebuild", ("rebuild.dispatch",)


def read(facts):
    return stages.share(facts, OP, ROWS)
