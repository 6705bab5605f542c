"""EC file pipeline: the coder's encode call as the pipeline sees it (H2D
issue, dispatch, the fence's wait), as a share of the window (stage rows:
seal.dispatch)."""

from benchmark import stages

OP, ROWS = "ec.encode", ("seal.dispatch",)


def read(facts):
    return stages.share(facts, OP, ROWS)
