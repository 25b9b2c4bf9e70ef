"""Decode per window: the union of the program's ``kafka.poll`` (the
source's resequencing and hand-off lists), ``kafka.decode`` (the commit
tap's control scan and native decode), ``decode`` (the same off the
broker) and ``decode.materialize`` (per-record ``Point`` objects for the
join's flatten path) spans in the window, over the windows emitted in it."""

import stages


def read(ctx):
    return stages.per_window_ms(ctx, stages.union_s(ctx.trace, stages.DECODE))
