"""Share of the local-training steps the bucket scans run that a sampled
client needs: the round records' ``local_steps`` (each client's unpadded
SGD steps) over ``scan_steps`` (rows times padded steps, shard padding
included), summed over the window's rounds.  Both are counted on the
host from the round plan.  Absent where the program counts neither."""


def read(ctx):
    counts = [r["counts"] for r in ctx.records
              if "scan_steps" in r.get("counts", {})]
    scan = sum(c["scan_steps"] for c in counts)
    if not scan:
        return None
    return 100.0 * sum(c.get("local_steps", 0) for c in counts) / scan
