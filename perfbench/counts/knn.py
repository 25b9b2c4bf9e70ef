"""Operations and bytes that one window-kNN call needs (``ops/knn.py``,
``knn_point_stats``): every point of the window is read once -- x and y
(float32), its cell and id (int32) and its valid flag (1 byte) -- and its
distance to the query point taken: two differences, two products, a sum and
a square root. Selection over the distances is not counted: it adds no
bytes the call must move, and its work is the kernel's own choice."""

POINT_BYTES = 4 + 4 + 4 + 4 + 1
DIST_FLOPS = 6


def count(points: int) -> tuple:
    """-> (floating-point operations, bytes)."""
    return DIST_FLOPS * points, POINT_BYTES * points
