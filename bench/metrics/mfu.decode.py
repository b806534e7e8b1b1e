"""The whole decode step's share of the chip's bf16 peak, in percent: the
model FLOPs of the tokens every decode step in the window processed (one
per active lane, at its context) over the steps' summed time (host clock
around ``block_until_ready``) × chips × peak."""
from bench import work


def read(run):
    if run.peak is None:         # no published peak: not a chip
        return None
    flops = secs = 0.0
    for t_end, dt, _, ctxs in run.steps:
        if run.t0 <= t_end <= run.t_end:
            flops += sum(work.flops_per_token(run.conf, c) for c in ctxs)
            secs += dt
    if not secs:
        return None
    return 100.0 * flops / (secs * run.chips * run.peak["bf16_flops"])
