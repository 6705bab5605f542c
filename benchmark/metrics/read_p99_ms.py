"""rpc plane + volume engine: 99th percentile of the window's reads,
client clock."""


def read(facts):
    req = facts["requests"]
    return req["p99_ms"] if req and req["op"] == "read" else None
