"""EC file pipeline: np.asarray and tobytes of the rebuilt rows (D2H), as a
share of the window (stage rows: rebuild.drain)."""

from benchmark import stages

OP, ROWS = "ec.rebuild", ("rebuild.drain",)


def read(facts):
    return stages.share(facts, OP, ROWS)
