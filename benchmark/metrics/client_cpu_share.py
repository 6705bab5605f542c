"""load generator: the generator processes' CPU over window x cores.
Says when the generator, not the server, set the rate."""


def read(facts):
    req = facts["requests"]
    return req["client_cpu_share"] if req else None
