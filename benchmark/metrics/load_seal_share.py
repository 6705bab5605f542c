"""EC file pipeline, under request load: how much of the window a seal
ran — the seal's main-thread stage rows (every `seal.` row but
`seal.stack`, the read-ahead thread beside it) over the window."""

from benchmark import stages

OP = "ec.encode"


def read(facts):
    rows = [r for r in facts["coder_rows"]
            if r.startswith("seal.") and r not in stages.BESIDE_MAIN]
    return stages.share(facts, OP, rows)
