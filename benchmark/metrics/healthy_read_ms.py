"""volume engine (EC read path): the server's time per GET of an
erasure-coded needle whose intervals were all read from shards (row
`read.healthy`: seconds over count): what `degraded_read_ms` stands
beside."""

ROW = "read.healthy"


def read(facts):
    row = facts["coder_rows"].get(ROW)
    return 1e3 * row["seconds"] / row["count"] if row and row["count"] \
        else None
