"""rpc plane + volume engine: the server's time per needle request,
admission to the response written, for requests answered with no EC
admin job running (row `req.alone`: seconds over count)."""

ROW = "req.alone"


def read(facts):
    row = facts["coder_rows"].get(ROW)
    return 1e3 * row["seconds"] / row["count"] if row and row["count"] \
        else None
