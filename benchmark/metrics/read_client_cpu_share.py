"""load generator: the generator processes' CPU over window x cores
(`client_cpu_share` under this cell's own name): a reader checks a
digest of every answer, up to 4 MiB each."""


def read(facts):
    req = facts["requests"]
    return req["client_cpu_share"] if req and req["op"] == "read" else None
