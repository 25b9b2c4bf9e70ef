"""Blocking readback per window: the program's ``<query>.merge`` spans
(``operators/base.py``, around ``Deferred.finish``) in the window, summed,
over the windows emitted in it."""


def read(ctx):
    sec, n = ctx.trace.span_sum_s(".merge")
    windows = len(ctx.markers_in_window())
    return 1e3 * sec / windows if n and windows else None
