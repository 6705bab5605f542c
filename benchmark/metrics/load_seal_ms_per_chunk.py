"""EC file pipeline, under request load: what one (10, 4 MiB) chunk
costs the seal's main thread — the wait for the reader, the coder call,
the D2H and both writes (stage rows) over the chunks dispatched."""

OP = "ec.encode"
ROWS = ("seal.stack_wait", "seal.dispatch", "seal.write_data",
        "seal.drain", "seal.write_parity")


def read(facts):
    jobs, rows = facts["jobs"], facts["coder_rows"]
    if not jobs or jobs["op"] != OP:
        return None
    chunks = rows.get("seal.dispatch", {}).get("count")
    if not chunks:
        return None
    return 1e3 * sum(rows[r]["seconds"] for r in ROWS if r in rows) / chunks
