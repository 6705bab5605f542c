"""rpc plane + volume engine: 99th percentile of the window's writes
(assign + upload), client clock."""


def read(facts):
    req = facts["requests"]
    return req["p99_ms"] if req and req["op"] == "write" else None
