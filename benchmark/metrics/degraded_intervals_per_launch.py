"""volume engine (EC read path): lost shard intervals rebuilt per launch
of the coder (row `read.interval`'s count over row `read.dispatch`'s):
over 1 where the lost intervals of one GET in one stripe row share a
launch; batching across GETs would raise it further."""

ROW, PER = "read.interval", "read.dispatch"


def read(facts):
    rows = facts["coder_rows"]
    row, per = rows.get(ROW), rows.get(PER)
    if not row or not per or not per["count"]:
        return None
    return row["count"] / per["count"]
