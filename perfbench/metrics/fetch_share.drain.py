"""Share of the window in which the consumer was inside a broker fetch:
the union of the program's ``kafka.fetch`` spans (``streams/kafka.py``)."""


def read(ctx):
    t = ctx.trace
    s = t.span_union_s({"kafka.fetch"})
    return 100.0 * s / t.window_s if s > 0 else None
