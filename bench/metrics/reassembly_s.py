"""Mean per round of the round record's ``fedsdd.local.reassemble`` span:
the host's work after the bucket scans are dispatched (trimming shard
padding, the per-leaf gather and concatenation into round order).
Absent where the program records no spans."""


def read(ctx):
    vals = [r["spans"]["fedsdd.local.reassemble"] for r in ctx.records
            if "fedsdd.local.reassemble" in r.get("spans", {})]
    return sum(vals) / len(vals) if vals else None
