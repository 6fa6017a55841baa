"""The benchmark's own tests run on the CPU only: JAX is held to the CPU
and the codecs run under the Pallas interpreter.  No test here describes
a TPU topology.

    python -m pytest perfbench/tests -q
"""

import pytest


@pytest.fixture(scope="session", autouse=True)
def cpu_only():
    """Before any test touches JAX: the CPU, and the interpreted codecs."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_PLATFORMS", "cpu")
        mp.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")
        yield
