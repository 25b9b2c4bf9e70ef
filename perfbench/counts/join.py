"""Operations and bytes that one window-join call needs (``ops/join.py``,
the lattice of ``join_pairs_host``): each side's points read once -- x and
y (float32), cell (int32), valid (1 byte) -- and one squared distance per
pair of the lattice: two differences, two products, a sum and a compare.
Pairs pruned by cell are still counted: the lattice the kernel is given is
the whole window on each side."""

POINT_BYTES = 4 + 4 + 4 + 1
PAIR_FLOPS = 6


def count(points_a: int, points_b: int) -> tuple:
    """-> (floating-point operations, bytes)."""
    return (PAIR_FLOPS * points_a * points_b,
            POINT_BYTES * (points_a + points_b))
