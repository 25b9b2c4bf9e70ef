"""Share of the window in which the first device ran nothing and no program
span was open (``stages.NAMES`` and the ``<query>.window``, ``.dispatch``
and ``.merge`` spans): the idle time the stage spans leave unexplained. It
is read from the first recorded window pull's start to the last one's end,
where every span is whole (``stages``); a trace with no window pull reads
None."""

import stages


def read(ctx):
    st = stages.stretch(ctx.trace)
    if st is None or st[1] <= st[0]:
        return None
    return 100.0 * stages.unattributed_s(ctx.trace, *st) / (st[1] - st[0])
