"""rpc plane + volume engine: the longest time inside the window in
which no read of any client was answered, client clock
(`longest_stall_ms` under this cell's own name).  A program that
compiles inside a GET stalls every client for seconds."""


def read(facts):
    req = facts["requests"]
    return req["longest_stall_ms"] if req and req["op"] == "read" else None
