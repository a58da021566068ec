"""The neighbour mix's share of its roofline: the least time its work
(each applied update's neighbour rows) needs over the obs.gather_mix
span's device time."""

from bench import readers


def read(ctx):
    return readers.share_of_least(
        ctx, "mix_flops", "mix_bytes", readers.scope_seconds(ctx, "gather_mix")
    )
