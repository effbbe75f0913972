"""Model FLOPs of the window's rounds over their host-clock time and the
chip's bf16 peak.  A round's FLOPs are each sampled client's required
local steps (a training step is three forwards), the K*R teacher
forwards over the server set and the KD steps; padded no-op steps are
not counted.  The sampled clients come from the job's schedule, which
the harness checks against the program's size probe in the set-up."""


def read(ctx):
    if not ctx.peaks or not ctx.round_flops:
        return None
    seconds = sum(ctx.round_seconds)
    return 100.0 * sum(ctx.round_flops) / (seconds
                                           * ctx.peaks["bf16_flops_per_s"])
