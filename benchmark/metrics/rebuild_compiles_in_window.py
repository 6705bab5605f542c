"""jit / shapes: XLA compilations the server logged inside the window
(JAX_LOG_COMPILES lines between two marks of its log).  Should read 0."""


def read(facts):
    return facts["compiles"]["count"]
