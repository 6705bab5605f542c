"""rpc plane + volume engine: the server's time per needle request,
admission to the response written, for requests answered while an EC
admin job ran in the process (row `req.beside_job`: seconds over
count)."""

ROW = "req.beside_job"


def read(facts):
    row = facts["coder_rows"].get(ROW)
    return 1e3 * row["seconds"] / row["count"] if row and row["count"] \
        else None
