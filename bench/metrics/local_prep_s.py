"""Mean per round of the round record's ``fedsdd.local.prep`` span: the
host's work before local training is dispatched (planning the buckets,
stacking their shards, gathering the start parameters, initialising the
optimiser state, padding).  Absent where the program records no spans."""


def read(ctx):
    vals = [r["spans"]["fedsdd.local.prep"] for r in ctx.records
            if "fedsdd.local.prep" in r.get("spans", {})]
    return sum(vals) / len(vals) if vals else None
