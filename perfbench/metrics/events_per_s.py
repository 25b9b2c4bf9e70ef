"""Drain throughput: the input events that the windows emitted inside the
window cover, between the first and the last of those emissions, over the
wall time between them (the broker's append times of their commit
markers). Counting whole windows would be too coarse: one 5 s slide is
500,000 events."""

import numpy as np


def read(ctx):
    marks = ctx.markers_in_window()
    if len(marks) < 2:
        return None
    (t_a, _s, end_a), (t_b, _s2, end_b) = marks[0], marks[-1]
    if t_b <= t_a:
        return None
    events = sum(int(np.searchsorted(s.ts, end_b) - np.searchsorted(s.ts, end_a))
                 for s in ctx.streams)
    return events / ((t_b - t_a) / 1e3)
