"""Window assembly per window: the self time of the ``<query>.window`` spans
(each pull of the next window, ``operators/base.py`` and
``operators/join_query.py``) -- their duration less the union of the program
spans nested in them (fetch, poll, decode, and on the join its dispatch and
pair extraction) -- over the pulls the trace recorded whole. The pulls in
progress when the window opens and closes are not recorded (``stages``)."""

import stages


def read(ctx):
    total, n = stages.self_s(ctx.trace), len(stages.pulls(ctx.trace))
    return 1e3 * total / n if n else None
