"""rpc plane + volume engine: of the needle requests the server
answered, the share answered while an EC admin job ran (rows
`req.beside_job` and `req.alone`: counts).  Under 5 % and the
requests' p95 cannot see the job."""

BESIDE, ALONE = "req.beside_job", "req.alone"


def read(facts):
    rows = facts["coder_rows"]
    if BESIDE not in rows and ALONE not in rows:
        return None
    beside = rows.get(BESIDE, {}).get("count", 0)
    total = beside + rows.get(ALONE, {}).get("count", 0)
    return 100.0 * beside / total if total else None
