"""The EC file pipeline's own stage rows as shares of the window.

The program counts what the host thread of a seal or a rebuild is doing,
stage by stage (`seaweedfs_tpu/stats/roofline.py` STAGES), and serves the
totals among the rows of `/debug/device`; the harness diffs them across
the window into `facts["coder_rows"]`.  A share is a stage's seconds over
the client's window, in %.  The main-thread stages of a job do not
overlap, so their shares and the unspanned share sum to 100: what is
unspanned is the window outside the pipeline (the shell in the harness,
HTTP, the master's lookups, heartbeats).  `seal.stack` is the read-ahead
thread, busy beside the main thread, and is in no sum.

A program without these rows (any commit before they were added) reads
as None: the metric is left out of the line.
"""

BESIDE_MAIN = ("seal.stack",)


def share(facts, op, rows):
    """Summed seconds of `rows` over the window, in %; None for another
    job or where the program served none of the rows."""
    jobs = facts["jobs"]
    if not jobs or jobs["op"] != op:
        return None
    got = [facts["coder_rows"][r]["seconds"] for r in rows
           if r in facts["coder_rows"]]
    return 100.0 * sum(got) / facts["window_s"] if got else None


def unspanned_share(facts, op, prefix):
    """The window less every main-thread stage whose name starts with
    `prefix`, in %."""
    rows = [r for r in facts["coder_rows"]
            if r.startswith(prefix) and r not in BESIDE_MAIN]
    spanned = share(facts, op, rows)
    return None if spanned is None else 100.0 - spanned
