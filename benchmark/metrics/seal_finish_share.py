"""EC file pipeline: what ends a seal on the server after the last chunk
(close of 14 files, .vif, .ecc, .ecx; the shards' mount; the original's
deletion), as a share of the window (stage rows: seal.finish, seal.mount,
seal.delete_original)."""

from benchmark import stages

OP = "ec.encode"
ROWS = ("seal.finish", "seal.mount", "seal.delete_original")


def read(facts):
    return stages.share(facts, OP, ROWS)
