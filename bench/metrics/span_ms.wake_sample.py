"""Device time per super-tick of the ops in the obs.wake_sample span."""

from bench import readers


def read(ctx):
    return readers.span_ms(ctx, ("wake_sample",))
