"""Device key sort: the TPU stage of the GraySort-analog sort pipeline.

Reference analog: the GraySort result (README.md:38-40) is produced by
smallpond running a two-phase partition sort *on CPUs* with 3FS as the
shuffle medium.  t3fs keeps the same two-phase shape (benchmarks/
sort_bench.py) but makes the per-partition key sort offloadable to the
accelerator, like the codec: records carry 10-byte keys (gensort layout);
the device sorts key columns and returns the gather permutation, and the
host applies it to the 100-byte payload rows.

TPU mapping: a 10-byte big-endian key splits into three uint32 lexicographic
columns (4+4+2 bytes).  `jax.lax.sort` with `num_keys=3` sorts the column
tuple and drags a row-index operand along, yielding the permutation in one
fused XLA sort (radix-style on TPU, no host compare loop).  uint32 avoids
the x64 flag; the 2-byte tail column zero-extends.

Economics note: the 16 B/record key traffic to the device is ~6% of the
100 B/record payload the host touches anyway; whether the device sort
beats the host end to end is not yet measured on the chip (ROADMAP A2).  The numpy path (`lexsort_rows`) is the oracle and the default
backend of sort_bench.
"""

from __future__ import annotations

import numpy as np

KEY_LEN = 10
REC_LEN = 100


def key_columns(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(n, REC_LEN) uint8 rows -> three uint32 lexicographic key columns."""
    assert rows.dtype == np.uint8 and rows.ndim == 2
    k0 = rows[:, 0:4].copy().view(">u4").ravel().astype(np.uint32)
    k1 = rows[:, 4:8].copy().view(">u4").ravel().astype(np.uint32)
    k2 = rows[:, 8:10].copy().view(">u2").ravel().astype(np.uint32)
    return k0, k1, k2


def lexsort_rows(rows: np.ndarray) -> np.ndarray:
    """Oracle/CPU backend: permutation sorting rows by their 10-byte key."""
    k0, k1, k2 = key_columns(rows)
    return np.lexsort((k2, k1, k0))


def make_device_sorter():
    """Returns sort_perm(rows: (n,REC_LEN) uint8 np.ndarray) -> (n,) int32
    permutation, computed on the default JAX device.

    Shapes are bucketed to powers of two (XLA compiles once per bucket, not
    once per row count): keys pad with 0xFF sentinels, which sort last —
    and on a tie with a real all-0xFF key, sort stability plus the padded
    rows' larger dragged indices still keeps every real row first — so
    dropping perm entries >= n recovers the exact permutation."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _perm(k0, k1, k2):
        idx = jnp.arange(k0.shape[0], dtype=jnp.int32)
        _, _, _, perm = jax.lax.sort((k0, k1, k2, idx), num_keys=3,
                                     is_stable=True)
        return perm

    def sort_perm(rows: np.ndarray) -> np.ndarray:
        n = len(rows)
        if n == 0:
            return np.empty(0, dtype=np.int64)
        k0, k1, k2 = key_columns(rows)
        m = 1 << max(10, (n - 1).bit_length())
        if m > n:
            k0 = np.concatenate([k0, np.full(m - n, 0xFFFFFFFF, np.uint32)])
            k1 = np.concatenate([k1, np.full(m - n, 0xFFFFFFFF, np.uint32)])
            k2 = np.concatenate([k2, np.full(m - n, 0xFFFFFFFF, np.uint32)])
        perm = np.asarray(_perm(jnp.asarray(k0), jnp.asarray(k1),
                                jnp.asarray(k2)))
        return perm[perm < n] if m > n else perm

    return sort_perm
