"""volume engine (EC read path): the survivors' reads into the pooled
host buffer, per GET that reached the third rung (row `read.gather`'s
seconds over row `read.degraded`'s count)."""

ROW, PER = "read.gather", "read.degraded"


def read(facts):
    rows = facts["coder_rows"]
    row, per = rows.get(ROW), rows.get(PER)
    if not row or not per or not per["count"]:
        return None
    return 1e3 * row["seconds"] / per["count"]
