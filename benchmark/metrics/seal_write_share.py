"""EC file pipeline: tobytes and write of the 14 shards' chunks, as a share of
the window (stage rows: seal.write_data, seal.write_parity)."""

from benchmark import stages

OP, ROWS = "ec.encode", ("seal.write_data", "seal.write_parity")


def read(facts):
    return stages.share(facts, OP, ROWS)
