"""Least HBM traffic of one run of the odd-even kernel over a shard.

The kernel reads the counts gathered into order position and the order
permutation, ``[num_rows, capacity]`` int32 each, and writes both back:
four arrays of 4-byte words.  It does no arithmetic worth counting
against a peak, so it is bound by bytes.
"""


def bytes_moved(num_rows: int, capacity: int) -> int:
    return 4 * num_rows * capacity * 4
