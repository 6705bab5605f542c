"""EC file pipeline: seconds inside the coder's fenced calls (H2D +
kernel + D2H; /debug/device rows ("reconstruct_kernel",)) over the window."""

ROWS = ("reconstruct_kernel",)
OP = "ec.rebuild"


def read(facts):
    jobs = facts["jobs"]
    if not jobs or jobs["op"] != OP:
        return None
    secs = sum(facts["coder_rows"].get(r, {}).get("seconds", 0.0)
               for r in ROWS)
    return 100.0 * secs / facts["window_s"] if secs else None
