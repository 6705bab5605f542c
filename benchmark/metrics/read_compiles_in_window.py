"""jit / shapes: XLA compilations the server logged inside the window
(`req_compiles_in_window` under this cell's own name).  Reads 0 where no
GET compiles: the degraded read has one program a width, compiled as the
server comes up."""


def read(facts):
    req = facts["requests"]
    return facts["compiles"]["count"] if req and req["op"] == "read" \
        else None
