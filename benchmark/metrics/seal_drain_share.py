"""EC file pipeline: np.asarray of the parity and CRC handles (D2H and any
wait for the device), as a share of the window (stage rows: seal.drain)."""

from benchmark import stages

OP, ROWS = "ec.encode", ("seal.drain",)


def read(facts):
    return stages.share(facts, OP, ROWS)
