"""The join's pair extraction per window: the union of ``join.reduce`` (the
pre-pass launch and its count readback), ``join.compact`` (the row
compaction), ``join.lattice`` (each mask tile's launch, readback and
``nonzero``) and ``join.pairs`` (the pair tuples) spans in the window
(``ops/join.py``, ``operators/join_query.py``), over the windows emitted in
it."""

import stages


def read(ctx):
    return stages.per_window_ms(ctx, stages.union_s(ctx.trace, stages.EXTRACT))
