"""What the grouped expert product cannot avoid: operations and bytes of
one launch of ``moe_grouped_matmul`` from its shapes.  No JAX.

A launch multiplies ``R`` routed rows, sorted by expert, each by its own
expert's ``[H, I]`` (gate, up) or ``[I, H]`` (down) matrix: ``2 R H I``
operations whichever way round.  It reads the rows once, writes the result
once, and reads all ``E`` experts' weights once.  Two things make this an
overcount of what a launch had to do, so the share reads high, never low:
an expert that no row was routed to is not read at all (at the cells' sizes,
2048 rows over 8 experts, that does not happen), and ``R`` is the rows of
the result, which include rows sorted behind the last group (the padding of
a packed step, and up to 127 rows of alignment) that the kernel skips.
"""

from __future__ import annotations

from typing import Tuple


def grouped_matmul(r: int, e: int, h: int, i: int, dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one grouped product of ``r`` result rows."""
    flops = 2.0 * r * h * i
    nbytes = float(dtype_bytes) * (e * h * i + r * h + r * i)
    return flops, nbytes
