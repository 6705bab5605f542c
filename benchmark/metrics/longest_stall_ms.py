"""rpc plane + volume engine: the longest time inside the window in
which no request of any client was answered (client clock).  A run whose
rate reads a quarter low with every tail in place had one such stall."""


def read(facts):
    req = facts["requests"]
    return req["longest_stall_ms"] if req else None
