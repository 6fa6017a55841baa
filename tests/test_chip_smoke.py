"""chip_smoke.py: refuses to run without a TPU, and its phases pass at a
tiny size on the CPU through the Pallas interpreter (the same phase code
the chip runs at full size)."""

import asyncio
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_phases_tiny(monkeypatch):
    monkeypatch.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    from t3fs.client import ec_codec
    from t3fs.storage.codec_backend import DeviceChecksumBackend

    # at most 4 stripes in flight, so warming batches of 1, 2 and 4 covers
    # every shape; the interpreter makes larger batches slow
    monkeypatch.setattr(ec_codec, "batch_ladder", lambda cap, base: [1, 2, 4])
    sizes = cs.Sizes(craq_chunks=4, craq_chunk=64 << 10, ckpt_chunk=4096,
                     ckpt_layers=2, ckpt_width=16, window=4, msr_stripes=4,
                     msr_chunk=16 << 10)
    lines = []
    counts = asyncio.run(cs.run_phases(
        sizes, 0,
        lambda: DeviceChecksumBackend(max_batch=4, interpret_on_cpu=True),
        lines.append, cs.CompileLog()))
    assert [ln.split(":")[0] for ln in lines] == [
        "warm-up", "craq x3", "ckpt rs8+2", "node loss", "pm-msr",
        "codec_counts"]
    assert counts["pallas-decode-words"] > 0
    assert counts["pallas-msr-repair"] > 0
    assert not set(cs.FALLBACK_CODECS) & set(counts)
