"""Ahead-of-time compiles of the served codec kernels for a TPU v5e chip.

Nothing runs: each kernel or jitted step is lowered and compiled for a
described (not attached) v5e device at the shapes chip_smoke.py serves
(n=32 stripes, real chunk sizes), so what Mosaic refuses — scoped-VMEM
overruns, unaligned tiles — fails here instead of on the chip.  Interpret
mode cannot see either.  The topology is described inside a fixture, never
at import, so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""

import os

import pytest

N = 32                       # ECCodec max_batch: the largest padded batch
MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2.  Skips only where the TPU compiler is not
    installed; any other failure to describe the chip fails the tests, so
    a broken libtpu or JAX cannot turn this guard into silent skips."""
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def compile_v5e(topo):
    """compile_v5e(fn, *shapes) -> compiled executable for one v5e chip.
    The persistent compile cache is off meanwhile: an entry compiled for a
    described chip cannot be read back here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _present(want):
    return tuple(s for s in range(10) if s not in want)[:8]


def test_encode_step_words_1mib(compile_v5e):
    from t3fs.ops.pallas_codec import make_stripe_encode_step_words
    W = MiB // 4
    compile_v5e(make_stripe_encode_step_words(W), (N, 8, W))


@pytest.mark.parametrize("chunk", [MiB, 4 * MiB], ids=["1MiB", "4MiB"])
@pytest.mark.parametrize("want", [(0, 1), (0, 9), (8, 9)],
                         ids=["data01", "data0+Q", "PQ"])
def test_decode_step_words(compile_v5e, chunk, want):
    """Two erasures, every kind: two data shards (the degraded read after
    losing two data nodes), data + parity, both parities."""
    from t3fs.ops.pallas_codec import make_stripe_decode_step_words
    W = chunk // 4
    compile_v5e(make_stripe_decode_step_words(W, _present(want), want),
                (N, 8, W))


def test_rs10p4_encode_step_words_1mib(compile_v5e):
    """HDFS's RS(10+4) Cauchy encode: 14 shards of in and out blocks."""
    from t3fs.ops.pallas_codec import make_stripe_encode_step_words
    W = MiB // 4
    compile_v5e(make_stripe_encode_step_words(W, 10, 4), (N, 10, W))


@pytest.mark.parametrize("want", [(0, 1, 2, 3), (0, 5, 10, 13), (7,)],
                         ids=["data0123", "mixed", "one"])
def test_rs10p4_decode_step_words(compile_v5e, want):
    """RS(10+4) Cauchy decode at 1 MiB chunks: four data shards lost (the
    degraded read after losing four data nodes), a mix, a single loss."""
    from t3fs.ops.pallas_codec import make_stripe_decode_step_words
    W = MiB // 4
    present = tuple(s for s in range(14) if s not in want)[:10]
    compile_v5e(make_stripe_decode_step_words(W, present, want, 10, 4),
                (N, 10, W))


def test_repair_step_words_1mib(compile_v5e):
    """Single-loss repair of a data shard from k survivors (dense row)."""
    from t3fs.ops.pallas_codec import make_repair_step_words
    from t3fs.ops.repair_program import schedule_repair_program
    from t3fs.ops.rs import default_rs
    present = tuple(range(1, 9))
    row = default_rs().reconstruct_gfmatrix(list(present), [0])[0]
    prog = schedule_repair_program(tuple(int(c) for c in row))
    W = MiB // 4
    compile_v5e(make_repair_step_words(W, prog),
                (N, prog.num_helpers, W))


def test_crc32c_words_raw_4mib(compile_v5e):
    """The storage node's CRC kernel at its largest bucket (n=64)."""
    from t3fs.ops.pallas_codec import make_crc32c_words_raw
    W = 4 * MiB // 4
    compile_v5e(make_crc32c_words_raw(W), (64, W))


def test_msr_encode_program_1mib(compile_v5e):
    from t3fs.ops.msr import default_msr
    from t3fs.ops.msr_codec import make_msr_encode_step
    code = default_msr()
    run = make_msr_encode_step(code, MiB, use_pallas=True)
    compile_v5e(run.program, (N, code.k, MiB // 4))


def test_msr_repair_program_1mib(compile_v5e):
    from t3fs.ops.msr import default_msr
    from t3fs.ops.msr_codec import make_msr_repair_step
    code = default_msr()
    run = make_msr_repair_step(code, 0, MiB, use_pallas=True)
    compile_v5e(run.program, (N, code.d, MiB // 2 // 4))


@pytest.mark.parametrize("kernel, program", [
    ("crc32c_seg_words", "raw_crc"),
    ("rs_encode_words", "encode"),
    ("rs_decode_words", "decode"),
])
def test_pallas_kernels_carry_their_names(compile_v5e, kernel, program):
    """Each served Pallas call is named, so a profile's operation names
    tell the CRC, encode and decode kernels apart inside one jitted step;
    the jitted programs keep the names the benchmark's roofline readers
    match (`jit_raw_crc`, `jit_step`)."""
    from t3fs.ops.pallas_codec import (make_crc32c_words_raw,
                                       make_stripe_decode_step_words,
                                       make_stripe_encode_step_words)
    W = 64 << 10                  # one 256 KiB chunk: names, not speed
    fn, shape, module = {
        "raw_crc": (make_crc32c_words_raw(W), (4, W), "jit_raw_crc"),
        "encode": (make_stripe_encode_step_words(W), (2, 8, W), "jit_step"),
        "decode": (make_stripe_decode_step_words(W, _present((0, 1)),
                                                 (0, 1)),
                   (2, 8, W), "jit_step"),
    }[program]
    text = compile_v5e(fn, shape).as_text()
    assert f"HloModule {module}," in text
    assert f"%{kernel}" in text
