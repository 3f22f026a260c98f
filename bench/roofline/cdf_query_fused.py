"""Least HBM traffic of one run of the fused query kernel.

Per real query the walk needs the row's counts and its order
(``capacity`` int32 each), the successors of the emission window
(``max_items``) and the row total, and it writes ``max_items`` successors
and probabilities and ``n_needed``.  Padding rows of the routed bucket are
not counted, so the bytes are a lower bound.
"""


def bytes_moved(queries: float, capacity: int, max_items: int) -> float:
    read = 4 * (2 * capacity + max_items + 1)
    write = 4 * (2 * max_items + 1)
    return queries * (read + write)
