"""volume engine (EC read path): the server's time per GET of an
erasure-coded needle that reached the degraded read's third rung for any
of its intervals, locate to the needle shaped (row `read.degraded`:
seconds over count)."""

ROW = "read.degraded"


def read(facts):
    row = facts["coder_rows"].get(ROW)
    return 1e3 * row["seconds"] / row["count"] if row and row["count"] \
        else None
