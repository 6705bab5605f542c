"""rpc plane + volume engine: the server process's CPU seconds over the
window (/proc, read once before and once after) per request answered."""


def read(facts):
    req = facts["requests"]
    if not req:
        return None
    done = req["attempted"] - req["failed"]
    return facts["server_cpu_s"] / done * 1e6 if done else None
