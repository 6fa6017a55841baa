#!/usr/bin/env python
"""Block-size sweep of the fused word-codec steps on one TPU chip.

For each kernel the served path runs — the fused decode+verify step
(`make_stripe_decode_step_words`), the fused encode+CRC step
(`make_stripe_encode_step_words`) and the fused sub-shard repair step
(`make_repair_step_words`) — and for the codec kernel alone inside each,
times every block size in --blocks at each (chunk, n) shape.  A block the
TPU lowering refuses (or that overflows scoped VMEM) is recorded with its
error instead of a time.

Timing: benchmarks/devbench.chained_timer — ITERS dependent executions in
one jitted loop, one scalar readback; each sample is seconds per iteration
and includes one xor pass over the input, the same for every block size,
so the rates compare blocks, not absolute kernel speed.  GB/s is input
bytes (n x helpers x chunk) over the median sample.

Prints one JSON line per row; --out also writes them all as one JSON file.

    python benchmarks/codec_block_sweep.py --out chiprun_out/blocks.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MiB = 1 << 20
K, M = 8, 2
DECODE_WANTS = [(0, 1), (8, 9), (0, 9), (3,)]


def _present(want: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(s for s in range(K + M) if s not in want)[:K]


def cases(kernels: list[str], chunk: int, block_w: int):
    """(kernel, label, fused step, kernel alone, helpers) at one block."""
    from t3fs.ops import pallas_codec as pc
    from t3fs.ops.repair_program import schedule_repair_program
    from t3fs.ops.rs import default_rs

    rs = default_rs(K, M)
    W = chunk // 4
    if "decode" in kernels:
        for want in DECODE_WANTS:
            present = _present(want)
            yield ("decode", list(want),
                   pc.make_stripe_decode_step_words(W, present, want,
                                                    block_w=block_w),
                   pc.make_rs_reconstruct_words_pallas(present, want, rs,
                                                       block_w=block_w), K)
    if "encode" in kernels:
        yield ("encode", None,
               pc.make_stripe_encode_step_words(W, block_w=block_w),
               pc.make_rs_encode_words_pallas(rs, block_w=block_w), K)
    if "repair" in kernels:
        # single-loss repair of data slot 0 from the k survivors
        row = rs.reconstruct_gfmatrix(list(range(1, K + 1)), [0])[0]
        prog = schedule_repair_program(tuple(int(c) for c in row))
        yield ("repair", [0],
               pc.make_repair_step_words(W, prog, block_w=block_w),
               pc.make_repair_subshard_words(prog, block_w=block_w),
               prog.num_helpers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="codec_block_sweep")
    ap.add_argument("--kernels", default="decode,encode,repair")
    ap.add_argument("--chunks", default="1048576",
                    help="chunk bytes, comma-separated")
    ap.add_argument("--ns", default="8", help="stripes per launch")
    ap.add_argument("--blocks", default="8192,16384,32768,65536,131072")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.devbench import chained_timer
    from t3fs.storage.codec_backend import enable_persistent_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"codec_block_sweep: no TPU: JAX's device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_persistent_cache()
    kernels = args.kernels.split(",")
    rng = np.random.default_rng(0)
    rows = []
    for chunk in map(int, args.chunks.split(",")):
        for n in map(int, args.ns.split(",")):
            inputs: dict[int, jax.Array] = {}
            for block_w in map(int, args.blocks.split(",")):
                for kernel, want, fused, alone, h in cases(kernels, chunk,
                                                           block_w):
                    if h not in inputs:
                        inputs[h] = jax.device_put(jnp.asarray(rng.integers(
                            0, 2**32, (n, h, chunk // 4), dtype=np.uint32)))
                    x = inputs[h]
                    row = {"kernel": kernel, "want": want, "chunk": chunk,
                           "n": n, "block_w": block_w}
                    try:
                        for name, op in (("fused", fused), ("alone", alone)):
                            t = chained_timer(op, x, iters=args.iters)
                            s = sorted(t() / args.iters
                                       for _ in range(args.reps))
                            row[f"{name}_s"] = s
                            row[f"{name}_GBps_median"] = \
                                x.nbytes / s[len(s) // 2] / 1e9
                    except Exception as e:   # a refused block is a result
                        row["error"] = str(e).splitlines()[0][:200]
                    print(json.dumps(row), flush=True)
                    rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
