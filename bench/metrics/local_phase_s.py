"""Mean of the round record's ``t_local`` over the window's rounds: local
training in the client engine, Eq. 2 and the teacher-bank push, ended in
``block_until_ready``."""


def read(ctx):
    vals = [r["t_local"] for r in ctx.records if "t_local" in r]
    return sum(vals) / len(vals) if vals else None
