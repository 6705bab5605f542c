"""EC file pipeline: what ends a rebuild on the server after the last
chunk (close of the shard files, the .ecc's load-modify-save; the
re-load of the volume's shards), as a share of the window (stage rows:
rebuild.finish, rebuild.mount)."""

from benchmark import stages

OP, ROWS = "ec.rebuild", ("rebuild.finish", "rebuild.mount")


def read(facts):
    return stages.share(facts, OP, ROWS)
