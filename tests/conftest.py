"""Test harness config: force JAX onto a virtual 8-device CPU mesh so all
sharding/collective paths are exercised without TPU hardware, with Pallas
kernels under the interpreter.  chip_smoke.py and bench.py run on the real
chip and must NOT import this.

Codec compiles go to the persistent compile cache where the code puts it
(t3fs.storage.codec_backend.enable_persistent_cache: the git-ignored
.jax_cache of this checkout unless JAX_COMPILATION_CACHE_DIR says
otherwise), so the xdist workers of one run share them instead of each
compiling cold.

On-device tier: T3FS_ON_DEVICE=1 keeps the REAL chip as the JAX backend so
the Pallas kernels compile with interpret=False (Mosaic) instead of the CPU
interpreter.  Intended for the device-test subset only, run on the chip:

    T3FS_ON_DEVICE=1 python -m pytest tests/test_pallas_codec.py \
        tests/test_codec_backend.py -q

Running the full suite in this mode is unsupported (most tests need the
8-device virtual CPU mesh)."""

import os

ON_DEVICE = bool(os.environ.get("T3FS_ON_DEVICE"))

# Sanitizer tier (`make sanitize`): ASan/TSan runtimes are LD_PRELOADed
# into python, and jaxlib's nanobind bindings trip the interceptors
# (__cxa_throw CHECK) — so the sanitizer pass, which targets the NATIVE
# code only, must not initialize jax at all.
SANITIZE = bool(os.environ.get("T3FS_SANITIZE"))

if not ON_DEVICE and not SANITIZE:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")

# Runtime race-detector tier (`T3FS_RACE_AUDIT=1`): every StorageFabric
# node gets a CriticalSectionAuditor on its audit hook, every
# ChunkReplica.apply_update runs in an audited section (covers the CRAQ
# step simulator too), and fabric lifetimes run under a LoopStallDetector
# — the runtime cross-check of t3fslint's static rules
# (docs/static_analysis.md).  Off by default: the hooks add per-update
# overhead and stall warnings would be noise on loaded CI machines.
RACE_AUDIT = os.environ.get("T3FS_RACE_AUDIT") == "1" and not SANITIZE

if RACE_AUDIT:
    import pytest  # noqa: E402

    @pytest.fixture(autouse=True)
    def _t3fs_race_audit():
        from t3fs.testing.race import race_audit

        with race_audit() as auditor:
            yield auditor


# --- per-test wall-clock watchdog -----------------------------------------
# One wedged test must cost ITS OWN failure, not the whole run: the suite
# ships under an overall `timeout -k 10 870` (ROADMAP tier-1), and a single
# lost-wakeup hang in a cluster test otherwise eats every remaining test's
# budget.  SIGALRM interrupts the main thread wherever it is (asyncio's
# select included); T3FS_TEST_TIMEOUT_S=0 disables.

import signal  # noqa: E402
import threading  # noqa: E402

TEST_TIMEOUT_S = int(os.environ.get("T3FS_TEST_TIMEOUT_S", "240"))


class TestWallclockTimeout(BaseException):
    """Raised by the watchdog.  BaseException, NOT Exception: hung tests
    often sit under broad `except Exception` recovery loops (mid-kill
    writers and the like), which must not swallow the abort."""


if TEST_TIMEOUT_S > 0 and hasattr(signal, "SIGALRM"):
    import faulthandler
    import sys

    import pytest  # noqa: E402,F811

    @pytest.hookimpl(wrapper=True)
    def pytest_runtest_call(item):
        if threading.current_thread() is not threading.main_thread():
            return (yield)

        def _on_alarm(signum, frame):
            faulthandler.dump_traceback(file=sys.stderr)
            # re-arm before raising: event-loop teardown after the abort
            # (asyncio.run cancelling tasks, fixture finalizers) can wedge
            # on the same condition the test did
            signal.alarm(60)
            raise TestWallclockTimeout(
                f"{item.nodeid}: exceeded {TEST_TIMEOUT_S}s wall clock")

        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(TEST_TIMEOUT_S)
        try:
            return (yield)
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
