"""The Eq. 4 row update's share of its roofline: the least time its work
(each applied update's own points and row) needs over the obs.row_update
span's device time."""

from bench import readers


def read(ctx):
    return readers.share_of_least(
        ctx, "row_flops", "row_bytes", readers.scope_seconds(ctx, "row_update")
    )
