"""rpc plane + volume engine: the server process's CPU seconds over the
window (/proc, read once before and once after) per read answered, the
coder's host work included (`server_cpu_us_per_req` under this cell's
own name: that entry moves `req_per_s`, which this cell does not
list)."""


def read(facts):
    req = facts["requests"]
    if not req or req["op"] != "read":
        return None
    done = req["attempted"] - req["failed"]
    return facts["server_cpu_s"] / done * 1e6 if done else None
