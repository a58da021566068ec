"""The whole super-tick's share of the chip's peak: the least time the
applied updates' work needs (all of its FLOPs and bytes, from the
deployment's sizes) over the super-tick program's device time. The bytes
bound decides it for this engine."""

from bench import readers


def read(ctx):
    return readers.share_of_least(ctx, "flops", "bytes", readers.supertick_seconds(ctx))
