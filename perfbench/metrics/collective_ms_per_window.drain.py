"""Collective time per window: the device time of the mesh program's
collective operations -- those whose name holds ``all-gather``,
``all_gather``, ``all-reduce`` or ``psum`` (the all-gather of the shards'
k-sized partials and the psum of the candidate count,
``parallel/ops.py`` ``knn_mesh_stats``) -- as the union of their intervals
in the window on each device, averaged over the devices, over the windows
emitted in it. A trace with no such operation reads None."""

import numpy as np

import stages
from devtrace import clip, union

MARKS = ("all-gather", "all_gather", "all-reduce", "psum")


def read(ctx):
    t = ctx.trace
    if not t.ops:
        return None
    per_dev, found = [], False
    for ops in t.ops.values():
        iv = np.array([(s, e) for s, e, n, _m in ops
                       if any(k in n for k in MARKS)],
                      np.float64).reshape(-1, 2)
        found = found or len(iv) > 0
        u = clip(union(iv), *t.window)
        per_dev.append(float((u[:, 1] - u[:, 0]).sum()))
    if not found:
        return None
    return stages.per_window_ms(ctx, float(np.mean(per_dev)))
