"""ChecksumBackend: the pluggable codec seam behind the storage write path.

This is the BASELINE.json north star — `chunk_engine.backend=tpu` — realized
as the seam the reference keeps for engine pluggability
(src/storage/store/StorageTarget.h:85-162, engine v1/v2 switch): storage
checksums flow through a backend chosen by config:

  cpu    — host CRC32C (native SSE4.2 when built, else the table oracle);
           large buffers hop to a thread so the event loop never blocks.
  tpu    — micro-batched device offload: concurrent update RPCs enqueue
           payloads, a worker drains the queue, buckets them by padded
           segment count, and runs ONE batched word-kernel call per bucket
           (t3fs.ops.pallas_codec.make_crc32c_words_raw); raw CRC is
           zero-preserving so buffers are front-padded and the true-length
           affine constant is applied per buffer on the host.  It runs the
           Mosaic kernels on a TPU and refuses any other platform; tests
           construct DeviceChecksumBackend(interpret_on_cpu=True) to run the
           same batching path through the Pallas interpreter on the CPU.
  null   — returns 0 and disables verification (reference
           FeatureFlags::BYPASS_* testability analog, fbs/storage/Common.h:72).

Reference CPU analog being replaced: folly::crc32c
(src/fbs/storage/Common.h:158).
"""

from __future__ import annotations

import asyncio
import functools
import logging
import os
import threading
import time
from collections import defaultdict
from concurrent.futures import CancelledError, ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from t3fs.ops.blocks import batch_ladder, pad_batch
from t3fs.ops.codec import crc32c as cpu_crc32c, crc32c_combine
from t3fs.ops.crc32c import default_matrices
from t3fs.utils import tracing
from t3fs.utils.aio import reap_task

log = logging.getLogger("t3fs.storage.codec")

# below this, the host CRC is cheaper than a device round trip
DEFAULT_MIN_DEVICE_BYTES = 64 << 10
SEG_BYTES = 512
SEG_WORDS = SEG_BYTES // 4
# payloads hop off the event loop above this even on the cpu backend
CPU_OFFLOAD_BYTES = 256 << 10


class ChecksumBackend:
    """Interface: async batched CRC32C for the storage node hot path."""

    name = "base"

    async def payload_crc(self, data: bytes) -> int:
        raise NotImplementedError

    def combine(self, a: int, b: int, len_b: int) -> int:
        """CRC32C of a concatenation from the parts' CRCs — the incremental
        rollup fragment streams use so per-fragment CRCs fold up to the
        chunk checksum without a second pass over the bytes (O(log n)
        matrix fold per fragment, no data touched)."""
        return crc32c_combine(a, b, len_b)

    @property
    def verify_enabled(self) -> bool:
        return True

    async def close(self) -> None:
        pass


class CpuChecksumBackend(ChecksumBackend):
    name = "cpu"

    async def payload_crc(self, data: bytes) -> int:
        if len(data) >= CPU_OFFLOAD_BYTES:
            return await asyncio.to_thread(cpu_crc32c, data)
        return cpu_crc32c(data)


class NullChecksumBackend(ChecksumBackend):
    name = "null"

    async def payload_crc(self, data: bytes) -> int:
        return 0

    def combine(self, a: int, b: int, len_b: int) -> int:
        return 0   # every checksum path must agree on 0 (see add_target)

    @property
    def verify_enabled(self) -> bool:
        return False


@dataclass
class _Pending:
    data: bytes
    future: asyncio.Future
    loop: asyncio.AbstractEventLoop
    # perf_counter at payload_crc entry and at the batch's launch, read
    # only for a caller under a span (0.0 otherwise)
    t0: float = 0.0
    t_launch: float = 0.0


class DeviceChecksumBackend(ChecksumBackend):
    """Micro-batching CRC32C offload to the JAX device.

    Batching across concurrent updates is what makes the device path win:
    one 512-byte-segment kernel call covers every payload that arrived
    within the batching window (reference batches writes at UpdateWorker;
    here the batch crosses chunks and chains)."""

    name = "device"

    def __init__(self, max_batch: int = 64, max_wait_us: int = 300,
                 min_device_bytes: int = DEFAULT_MIN_DEVICE_BYTES,
                 interpret_on_cpu: bool = False):
        self.max_batch = max_batch
        self.interpret_on_cpu = interpret_on_cpu
        self.max_wait_s = max_wait_us / 1e6
        self.min_device_bytes = min_device_bytes
        self._q: asyncio.Queue[_Pending] = asyncio.Queue()
        self._worker: asyncio.Task | None = None
        self._pool = ThreadPoolExecutor(1, thread_name_prefix="t3fs-codec")
        self._interpret: bool | None = None
        self._closed = False
        self.batches = 0
        self.batched_items = 0
        self.padded_rows = 0     # zero rows launched to reach a ladder size

    # --- public API ---

    def claim_device(self) -> None:
        """Initialise JAX's backend now and check that it is one this
        backend may use: a TPU, or the CPU when interpret_on_cpu is set.
        A storage node calls this at start, so a node that cannot use the
        chip (no TPU, or another process holds it: one process per chip)
        fails at start instead of on its first large write."""
        if self._interpret is not None:
            return
        import jax

        platform = jax.devices()[0].platform
        if platform == "tpu":
            self._interpret = False
        elif platform == "cpu" and self.interpret_on_cpu:
            self._interpret = True
        else:
            raise RuntimeError(
                f"checksum backend 'tpu' needs a TPU, but JAX's device is "
                f"{platform!r}; use checksum_backend='cpu' on this host")

    async def payload_crc(self, data: bytes) -> int:
        if self._closed:
            # fail fast: enqueueing after close() would RESTART the worker
            # below and either hang (pool gone) or fail late — shutdown
            # races surface as a clean backend-closed error instead
            raise make_closed_error()
        if len(data) < self.min_device_bytes:
            return cpu_crc32c(data)
        if self._worker is None or self._worker.done():
            self._worker = asyncio.get_running_loop().create_task(
                self._worker_loop())
        loop = asyncio.get_running_loop()
        sp = tracing.current_span()
        item = _Pending(data, loop.create_future(), loop,
                        time.perf_counter() if sp is not None else 0.0)
        await self._q.put(item)
        crc = await item.future
        if sp is not None and item.t_launch:
            # entry to launch (the batcher queue); launch to the caller
            # resuming (the double-buffer hold, H2D, kernel, D2H and the
            # hop back to the loop)
            sp.set_tag("crc_queue_s", item.t_launch - item.t0)
            sp.set_tag("crc_result_s", time.perf_counter() - item.t_launch)
        return crc

    async def close(self) -> None:
        self._closed = True
        if self._worker is not None:
            self._worker.cancel()
            await reap_task(self._worker, log, "device codec worker")
            self._worker = None
        # fail anything still queued so in-flight payload_crc() awaits don't
        # hang a node shutdown under write load
        err = make_closed_error()
        while not self._q.empty():
            item = self._q.get_nowait()
            if not item.future.done():
                item.future.set_exception(err)
        # cancel_futures drops queued warmup compiles; only an in-flight
        # one (bounded: a single compile) is waited for
        self._pool.shutdown(wait=True, cancel_futures=True)

    # --- batching worker ---

    async def _worker_loop(self) -> None:
        """Double-buffered dispatch (docs/codec_economics.md: serial
        copy-then-compute can NEVER reach line rate; overlap can): batch
        n+1's host pack + H2D + kernel LAUNCH happens before batch n's
        results are pulled, so on a real chip the device computes n while
        the host prepares n+1 (JAX async dispatch makes the launch
        non-blocking; only the result pull blocks)."""
        loop = asyncio.get_running_loop()
        batch: list[_Pending] = []
        in_flight: list | None = None       # dispatched, results not pulled
        try:
            while True:
                try:
                    if in_flight is None:
                        first = await self._q.get()
                    else:
                        # traffic pause: bound how long the in-flight
                        # batch's callers wait for their CRCs
                        first = await asyncio.wait_for(self._q.get(),
                                                       self.max_wait_s)
                except asyncio.TimeoutError:
                    await loop.run_in_executor(self._pool, self._resolve,
                                               in_flight)
                    in_flight = None
                    continue
                batch = [first]
                deadline = loop.time() + self.max_wait_s
                while len(batch) < self.max_batch:
                    timeout = deadline - loop.time()
                    if timeout <= 0:
                        break
                    try:
                        batch.append(
                            await asyncio.wait_for(self._q.get(), timeout))
                    except asyncio.TimeoutError:
                        break
                groups: dict[int, list[_Pending]] = defaultdict(list)
                for item in batch:
                    groups[self._bucket_words(len(item.data))].append(item)
                self.batches += len(groups)
                self.batched_items += len(batch)
                if any(item.t0 for item in batch):   # a caller is traced
                    t_launch = time.perf_counter()
                    for item in batch:
                        item.t_launch = t_launch
                try:
                    dispatched = await loop.run_in_executor(
                        self._pool, self._dispatch, groups)
                except Exception as e:  # pragma: no cover - device failure
                    log.exception("device CRC dispatch failed; failing batch")
                    for item in batch:
                        item.loop.call_soon_threadsafe(
                            _set_exception_safe, item.future, e)
                    dispatched = None
                batch = []
                # pull the PREVIOUS batch only now — its kernel ran on the
                # device while this batch was packed and launched
                if in_flight is not None:
                    await loop.run_in_executor(self._pool, self._resolve,
                                               in_flight)
                in_flight = dispatched
        except asyncio.CancelledError:
            # fail whatever was collected or still in flight
            err = make_closed_error()
            for item in batch:
                if not item.future.done():
                    item.future.set_exception(err)
            if in_flight is not None:
                for items, _res in in_flight:
                    for item in items:
                        if not item.future.done():
                            item.future.set_exception(err)
            raise

    @staticmethod
    def _bucket_words(nbytes: int) -> int:
        """Pad to a power-of-two number of 512-byte segments (bounded set of
        compiled shapes, mirroring the engine's size-class ladder)."""
        segs = max(1, -(-nbytes // SEG_BYTES))
        p = 1
        while p < segs:
            p <<= 1
        return p * SEG_WORDS

    def _kernel(self, chunk_words: int) -> "_CrcKernel":
        self.claim_device()
        return _crc_kernel(chunk_words, self._interpret)

    def warmup(self, payload_sizes: list[int]) -> int:
        """Precompile (and persist) the kernels for the given payload sizes
        across all n-buckets — call off-path (bench setup, server start).
        Runs each compile as its own job on the codec thread so close()
        (shutdown with cancel_futures) drops whatever hasn't started; a
        closed backend stops compiling after at most the in-flight one.
        A failed compile raises: that size would fail on the write path
        too.  Returns the number of (size, bucket) programs warmed."""
        def one(chunk_words: int, nb: int) -> None:
            if self._closed:
                return
            kern = self._kernel(chunk_words)
            with kern.lock:              # one compile per shape per process
                if nb not in kern.warmed:
                    arr = np.zeros((nb, chunk_words), dtype=np.uint32)
                    np.asarray(kern.fn(arr))
                    kern.warmed.add(nb)

        futs = []
        for chunk_words in sorted({self._bucket_words(s)
                                   for s in payload_sizes}):
            for nb in batch_ladder(self.max_batch):
                if self._closed:
                    break
                try:
                    futs.append(self._pool.submit(one, chunk_words, nb))
                except RuntimeError:   # pool already shut down
                    break
        for f in futs:
            try:
                f.result()
            except CancelledError:
                break
        return len(futs)

    def _dispatch(self, groups: dict[int, list[_Pending]]) -> list:
        """Codec thread, NON-blocking on the device: pack + launch one
        kernel per bucket and return the lazy device results."""
        out = []
        for chunk_words, items in groups.items():
            n = pad_batch(len(items), self.max_batch)
            self.padded_rows += n - len(items)
            arr = np.zeros((n, chunk_words * 4), dtype=np.uint8)
            for i, item in enumerate(items):
                # FRONT-pad: raw CRC is zero-preserving
                arr[i, arr.shape[1] - len(item.data):] = np.frombuffer(
                    item.data, dtype=np.uint8)
            out.append((items, self._kernel(chunk_words).fn(
                arr.view(np.uint32))))
        return out

    def _resolve(self, dispatched: list) -> None:
        """Codec thread: pull device results and deliver CRCs.  Failures
        are per-bucket — one bucket's device error must not strand the
        other buckets' callers."""
        mats = default_matrices()
        for items, res in dispatched:
            try:
                raw = np.asarray(res)
            except Exception as e:  # pragma: no cover - device failure
                log.exception("device CRC resolve failed; failing bucket")
                for item in items:
                    item.loop.call_soon_threadsafe(
                        _set_exception_safe, item.future, e)
                continue
            for i, item in enumerate(items):
                crc = int(raw[i]) ^ mats.affine_const(len(item.data))
                item.loop.call_soon_threadsafe(
                    _set_result_safe, item.future, crc)


# the checkout's root: the compile cache lives inside it unless
# JAX_COMPILATION_CACHE_DIR places it elsewhere
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_persistent_cache() -> None:
    """Keep compiled programs on disk so a kernel compile is paid once per
    checkout, not once per process (the first 4 MiB-bucket compile takes
    ~10 s — fatal to a freshly started storage node's latency otherwise).
    JAX reads JAX_COMPILATION_CACHE_DIR into jax_compilation_cache_dir;
    when that is unset, the cache goes to the fixed DEFAULT_CACHE_DIR (a
    fixed path, since the path is part of each entry's key).  Idempotent."""
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class _CrcKernel:
    """The jitted raw-CRC program for one padded chunk length.  Shared by
    every DeviceChecksumBackend in the process (an in-process cluster runs
    many storage nodes on one chip), so each shape compiles once; `warmed`
    holds the batch buckets already run, under `lock`."""

    def __init__(self, chunk_words: int, interpret: bool):
        import jax

        from t3fs.ops.pallas_codec import make_crc32c_words_raw

        enable_persistent_cache()
        self.fn = jax.jit(make_crc32c_words_raw(chunk_words,
                                                interpret=interpret))
        self.lock = threading.Lock()
        self.warmed: set[int] = set()


@functools.lru_cache(maxsize=None)
def _crc_kernel(chunk_words: int, interpret: bool) -> _CrcKernel:
    return _CrcKernel(chunk_words, interpret)


def make_closed_error() -> Exception:
    from t3fs.utils.status import StatusCode, make_error

    return make_error(StatusCode.INTERNAL, "checksum backend closed")


def _set_result_safe(fut: asyncio.Future, value: int) -> None:
    if not fut.done():
        fut.set_result(value)


def _set_exception_safe(fut: asyncio.Future, exc: Exception) -> None:
    if not fut.done():
        fut.set_exception(exc)


def make_checksum_backend(name: str | ChecksumBackend, **kw) -> ChecksumBackend:
    """Factory for the config seam: checksum_backend = cpu | tpu | null.

    "tpu" maps to the batching device backend, which runs only on a TPU.
    An already-constructed backend passes through (tests tune batching and
    run it under the Pallas interpreter)."""
    if isinstance(name, ChecksumBackend):
        return name
    if callable(name):
        # factory: a fresh backend per node (needed when each test runs its
        # own event loop — a backend's queue binds to the loop that uses it)
        return make_checksum_backend(name())
    if name in ("cpu", "", None):
        return CpuChecksumBackend()
    if name == "tpu":
        return DeviceChecksumBackend(**kw)
    if name == "null":
        return NullChecksumBackend()
    raise ValueError(f"unknown checksum backend {name!r}")
