"""rpc plane + volume engine: 95th percentile of the window's reads,
client clock — for a read mix whose tail is not steady enough to be held
to a bound as `req_p95_ms`."""


def read(facts):
    req = facts["requests"]
    return req["p95_ms"] if req and req["op"] == "read" else None
