"""Device time per super-tick of the ops in the obs.row_gather span (the
woken rows' per-agent constants: their padded points and Eq. 4 terms)."""

from bench import readers


def read(ctx):
    return readers.span_ms(ctx, ("row_gather",))
