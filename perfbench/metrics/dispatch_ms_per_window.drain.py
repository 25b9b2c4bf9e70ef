"""Dispatch per window: the program's ``<query>.dispatch`` spans (host batch
build, transfer and the asynchronous launch; ``operators/base.py``
``_drive_batched``, ``operators/join_query.py`` ``_join_window``) in the
window, summed, over the windows emitted in it."""

import stages


def read(ctx):
    return stages.per_window_ms(ctx, stages.sum_s(ctx.trace, ".dispatch"))
