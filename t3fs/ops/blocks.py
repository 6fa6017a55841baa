"""Kernel block-size arithmetic shared by the codec call sites.

jax-free on purpose: the EC codec imports this at module scope and must
stay importable under the sanitizer runs that cannot load jaxlib.
"""

from __future__ import annotations


def pick_block(total: int, preferred: int) -> int:
    """Largest divisor of `total` that is <= preferred (kernel block sizes
    must tile the axis exactly; chunk sizes are powers of two in practice
    but tests use arbitrary small lengths)."""
    b = min(preferred, total)
    while total % b:
        b -= 1
    return b


def pad_batch(n_items: int, cap: int, base: int = 4) -> int:
    """Rows a device batch of n_items is padded to: the next power of
    `base`, capped at the batcher's max batch.  Bounds the compiled shapes
    per kernel to a short ladder that warm-up can cover ({1, 4, 16, 64} at
    base 4 and cap 64); the waste, at most base - 1 zero rows per real
    one, is compute and copy on zero rows."""
    n = 1
    while n < n_items:
        n *= base
    return min(n, max(cap, n_items))


def batch_ladder(cap: int, base: int = 4) -> list[int]:
    """Every value pad_batch can return for batches of 1..cap items."""
    return sorted({pad_batch(i, cap, base) for i in range(1, cap + 1)})
