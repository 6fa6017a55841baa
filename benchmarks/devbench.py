"""Device micro-benchmark harness for one TPU kernel.

Wall-clock loops over separate dispatches mix per-dispatch overhead into
the kernel time.  This harness times a single jitted fori_loop that chains
ITERS dependent executions of the op and returns one scalar, timed
end-to-end including one host readback (amortized over ITERS).

Each iteration perturbs the input with a data-dependent scalar so XLA cannot
hoist the op out of the loop; the perturbation pass itself costs one
elementwise HBM round trip, measured separately by `overhead` and subtracted.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _build_chained(op, iters: int):
    def run(x0):
        def body(i, carry):
            x, acc = carry
            x = x ^ acc.astype(x.dtype)              # data-dep: no hoisting
            out = op(x)
            acc = jnp.uint32(0)
            for leaf in jax.tree_util.tree_leaves(out):
                # fold first AND last element of every output leaf: a single
                # element can slice through a concat and let XLA DCE the
                # pallas call feeding the other side
                flat = leaf.reshape(-1)
                acc = acc ^ flat[0].astype(jnp.uint32) \
                          ^ flat[-1].astype(jnp.uint32)
            acc = acc | jnp.uint32(1)
            return x, acc
        _, acc = jax.lax.fori_loop(0, iters, body, (x0, jnp.uint32(0)))
        return acc
    return jax.jit(run)


def chained_time(op, x, iters: int = 100, reps: int = 5) -> float:
    """Raw seconds per iteration of [xor-perturb pass + op(x)] on device.

    The xor pass (one elementwise HBM read+write of x) makes each iteration
    data-dependent on the last so XLA can't hoist or CSE the op; its cost is
    one full r+w pass over x — calibrate with a pallas copy kernel (whose
    loop = xor pass + copy pass, i.e. 2 identical passes) and subtract.

    op: fn(array) -> array or pytree.  Must be opaque to XLA (pallas_call);
    plain elementwise ops get DCE-sliced to the one element the carry reads.
    """

    fn = _build_chained(op, iters)
    _ = int(fn(x))                                   # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _ = int(fn(x))                               # readback = real sync
        ts.append(time.perf_counter() - t0)
    return min(ts) / iters


def chained_timer(op, x, iters: int = 100):
    """Like chained_time but returns a zero-arg callable timing ONE pass
    (compile+warm done here).  Lets callers interleave measurement and
    calibration reps so clock-drift on the device hits both
    equally instead of skewing the subtraction."""
    fn = _build_chained(op, iters)
    _ = int(fn(x))                                   # compile + warm

    def one() -> float:
        t0 = time.perf_counter()
        _ = int(fn(x))
        return time.perf_counter() - t0
    return one


def op_time(op, x, xor_pass_s: float, iters: int = 100) -> float:
    """Seconds per op(x), with the xor-perturb pass subtracted."""
    return max(chained_time(op, x, iters) - xor_pass_s, 1e-12)


def copy_calibrate(make_copy, x, iters: int = 100, reps: int = 5) -> float:
    """Returns the xor-pass time for arrays shaped like x: the copy loop is
    two identical r+w passes, so each is half the per-iter time."""
    return chained_time(make_copy, x, iters, reps) / 2.0


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + jnp.uint32(1)


def make_copy3d(x):
    """Pallas identity-ish pass over (n, k, W) uint32 — the calibration op."""
    from jax.experimental import pallas as pl

    n, k, W = x.shape
    v = x.reshape(n, k, W // 2048, 2048)
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(v.shape, jnp.uint32),
        grid=(n, W // 16384),
        in_specs=[pl.BlockSpec((1, k, 8, 2048), lambda i, j: (i, 0, j, 0))],
        out_specs=pl.BlockSpec((1, k, 8, 2048), lambda i, j: (i, 0, j, 0)),
    )(v)


if __name__ == "__main__":
    import numpy as np

    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.integers(0, 2**32, (16, 8, (1 << 20) // 4), dtype=np.uint32))
    nbytes = x.size * 4
    xor_s = copy_calibrate(make_copy3d, x)
    print(f"one r+w pass over {nbytes >> 20} MiB: {xor_s * 1e3:.3f} ms "
          f"-> {2 * nbytes / xor_s / 1e9:.0f} GB/s HBM (v5e peak ~819)")
