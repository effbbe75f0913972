"""Mean of the round record's ``t_kd`` over the window's rounds: the KD
pipeline's teacher precompute and KD scan, ended in
``block_until_ready``."""


def read(ctx):
    vals = [r["t_kd"] for r in ctx.records if "t_kd" in r]
    return sum(vals) / len(vals) if vals else None
