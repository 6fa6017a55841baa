"""EC stripe codec: the word-packed Pallas kernels behind the EC client.

The EC data path once encoded via `jax_codec` (the XLA bit-matmul path,
~10 GB/s) while bench.py shipped the fused word kernels.  This module
routes ECStorageClient's encode and reconstruct through the SAME kernels
the bench measures:

  - encode: `make_rs_encode_words_pallas` — the SWAR word kernel (the
    code's parity rows as a scheduled xtimes/xor program over uint32
    words), the parity half of bench.py's `make_stripe_encode_step_words`;
  - reconstruct: `make_rs_reconstruct_words_pallas` — the decode-side word
    kernel (GF(2^8) decode constants as the same kind of program);
  - reconstruct_verified: `make_stripe_decode_step_words` — the fused
    decode+verify step; one launch rebuilds the missing shards AND returns
    CRC32Cs of survivors + rebuilt, so degraded reads/repair pay no
    per-shard CPU crc32c after the device round trip.

Every call names its parity code (`code`, an RSCode.code_id; default
`rs.stored_code(k, m)`, what a new layout of that shape stores), and the
code is part of each jit-cache key.  Each operation has one device
formulation, the word kernels above, and one other: `jax_codec`'s XLA
bit-matmul, which serves every platform but the TPU (interpreted Pallas as
the only CPU path cost a 3-4x regression on CPU fabrics), every length the
word kernels cannot take (encode and reconstruct: no whole number of
words; the fused verified steps: no whole number of 512-byte segments),
and is the oracle.  T3FS_FORCE_PALLAS_INTERPRET=1 flips the suite onto
interpreted Pallas so the shipping kernels stay covered without
hardware.

Concurrent stripe operations MICRO-BATCH into one device call (same
pattern as storage/codec_backend.py batches CRCs): encode/reconstruct
requests that arrive within the batching window and share a shape key
are stacked along the batch axis and dispatched as a single kernel
launch — the batch axis is where the TPU path wins.  The stacked batch is
padded to the next power of two (blocks.pad_batch, base PAD_BASE), so each
key compiles at most six shapes (1..32) and warm-up can cover all of them.
Powers of two, not the CRC batcher's powers of four: a row here is a whole
stripe (8 MiB at 1 MiB chunks), so padding waste is H2D bytes and kernel
work, and the checkpoint writer's window of 8 stripes pads to itself.

The reference has no EC data path (its data_placement.py:484 EC is
placement-only); this capability is t3fs's own, so parity here means
internal consistency with bench.py's measured configuration.
"""

from __future__ import annotations

import asyncio
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable

import numpy as np

log = logging.getLogger("t3fs.client.ec_codec")

PAD_BASE = 2


@dataclass
class _Pending:
    rows: np.ndarray             # one request's shards (k, L)
    future: asyncio.Future
    loop: asyncio.AbstractEventLoop
    t0: float = 0.0              # perf_counter at _submit entry (sampling)


def _set_result_safe(fut: asyncio.Future, value) -> None:
    if not fut.done():
        fut.set_result(value)


def _set_exception_safe(fut: asyncio.Future, err) -> None:
    if not fut.done():
        fut.set_exception(err)


from t3fs.ops.blocks import batch_ladder, pad_batch
from t3fs.ops.blocks import pick_block as _pick_block
from t3fs.ops.rs import rs_code, stored_code
from t3fs.utils import tracing
from t3fs.utils.aio import reap_task


def _code(k: int, m: int, code: str | None) -> str:
    return code or stored_code(k, m)


class ECCodec:
    """Batched device codec for EC stripes with a per-shape jit cache.

    kind keys: ("enc", k, m, L, code), ("encv", k, m, L, code),
    ("rec", present, want, k, m, L, code), ("recv", present, want, k, m,
    L, code) — the fused decode+verify step — and ("rep", coeffs, k, m, L)
    — the scheduled single-row repair program; requests under one key
    stack into a single kernel call.
    """

    def __init__(self, max_batch: int = 32, max_wait_us: int = 300):
        self.max_batch = max_batch
        self.max_wait_s = max_wait_us / 1e6
        self._q: asyncio.Queue[tuple[tuple, _Pending]] = asyncio.Queue()
        self._worker: asyncio.Task | None = None
        self._pool = ThreadPoolExecutor(1, thread_name_prefix="t3fs-ec")
        self._fns: dict[tuple, Callable] = {}
        # extra `ec.flush` tags of an encode or decode key: its code, and
        # the gf_ops of the word program that serves it
        self._span_tags: dict[tuple, dict] = {}
        self._interpret: bool | None = None
        self._use_pallas: bool | None = None
        self._closed = False
        # observability: which codec implementation served each call
        # ("pallas-words" | "pallas-rec-words" | "pallas-decode-words" |
        #  "xla-bitmatmul" | ...); warmup compiles
        # count too (they run the same fns on the same codec thread)
        self.codec_counts: dict[str, int] = {}
        self.last_codec: str | None = None
        self.batches = 0
        self.batched_items = 0
        self.padded_rows = 0     # zero rows launched to reach a ladder size

    # --- public API (called from the event loop) ---

    async def encode(self, data_shards: np.ndarray, k: int, m: int,
                     code: str | None = None) -> np.ndarray:
        """(k, L) uint8 data shards -> (m, L) uint8 parity."""
        L = data_shards.shape[-1]
        return await self._submit(("enc", k, m, L, _code(k, m, code)),
                                  data_shards)

    async def encode_verified(self, data_shards: np.ndarray, k: int, m: int,
                              code: str | None = None
                              ) -> tuple[np.ndarray, np.ndarray]:
        """(k, L) uint8 data shards -> (parity (m, L) uint8,
        crcs (k+m,) uint32): parity + CRC32C of every shard (data first,
        then parity) from the SAME device launch — the write path hands
        the CRCs to write_chunk, so no host crc32c runs per shard."""
        L = data_shards.shape[-1]
        return await self._submit(("encv", k, m, L, _code(k, m, code)),
                                  data_shards)

    async def reconstruct(self, present_rows: np.ndarray,
                          present: tuple[int, ...], want: tuple[int, ...],
                          k: int, m: int, code: str | None = None
                          ) -> np.ndarray:
        """(k, L) uint8 present shards -> (len(want), L) uint8."""
        L = present_rows.shape[-1]
        return await self._submit(
            ("rec", present, want, k, m, L, _code(k, m, code)), present_rows)

    async def reconstruct_verified(self, present_rows: np.ndarray,
                                   present: tuple[int, ...],
                                   want: tuple[int, ...], k: int, m: int,
                                   code: str | None = None
                                   ) -> tuple[np.ndarray, np.ndarray]:
        """(k, L) uint8 present shards -> (rebuilt (len(want), L) uint8,
        crcs (k + len(want),) uint32): decode + CRC32C of survivors (in
        `present` order) and rebuilt shards (in `want` order), all from the
        SAME device launch — the degraded-read path pays no per-shard CPU
        crc32c after the round trip."""
        L = present_rows.shape[-1]
        return await self._submit(
            ("recv", present, want, k, m, L, _code(k, m, code)), present_rows)

    async def repair(self, helper_rows: np.ndarray, coeffs: tuple[int, ...],
                     k: int = 8, m: int = 2
                     ) -> tuple[np.ndarray, np.ndarray]:
        """(h, L) uint8 helper rows -> (rebuilt (L,) uint8, crc uint32).

        Evaluates one scheduled GF(2^8) repair program (coeffs[i] is helper
        i's coefficient; see ops/repair_program.py) — the reduced-read
        single-erasure path: helpers are whatever slices the read path
        fetched (sub-chunk ranges of survivors, or an LRC local group), NOT
        necessarily k full shards.  The returned CRC32C of the rebuilt
        bytes feeds crc32c_combine on the write-back path.  Requests with
        the same (coeffs, L) micro-batch into one launch, which is exactly
        the drill shape: many sub-shards of one lost chunk, one program."""
        L = helper_rows.shape[-1]
        key = ("rep", tuple(int(c) for c in coeffs), k, m, L)
        return await self._submit(key, helper_rows)

    # --- pm-msr (coupled-layer regenerating code; ops/msr.py) ---

    async def msr_encode_verified(self, data_shards: np.ndarray, k: int,
                                  m: int) -> tuple[np.ndarray, np.ndarray]:
        """(k, L) uint8 raw data shards -> (parity (m, L) uint8,
        crcs (k+m,) uint32) under the pm-msr coupled generator.  Data
        shards stay raw bytes on disk (systematic), so only the parity
        bytes differ from plain RS."""
        L = data_shards.shape[-1]
        return await self._submit(("mencv", k, m, L), data_shards)

    async def msr_repair(self, helper_rows: np.ndarray, failed_slot: int,
                         k: int = 8, m: int = 2
                         ) -> tuple[np.ndarray, np.ndarray]:
        """(d, beta_len) uint8 helper projections -> (rebuilt chunk (L,)
        uint8, crc uint32).  helper_rows holds, for each of the d = k+m-1
        survivors in ascending slot order, its beta selected sub-chunks
        concatenated in ascending plane order (the byte layout the
        projection read plan assembles); L = 2 * beta_len."""
        beta_len = helper_rows.shape[-1]
        key = ("mrep", int(failed_slot), k, m, 2 * beta_len)
        return await self._submit(key, helper_rows)

    async def msr_decode_verified(self, present_rows: np.ndarray,
                                  present: tuple[int, ...],
                                  want: tuple[int, ...], k: int, m: int
                                  ) -> tuple[np.ndarray, np.ndarray]:
        """(k, L) uint8 present pm-msr shards -> (rebuilt (len(want), L)
        uint8, crcs (k + len(want),) uint32) — the multi-loss / degraded
        full-k path (exactly k survivor shards, never more than RS)."""
        L = present_rows.shape[-1]
        return await self._submit(("mdecv", tuple(present), tuple(want),
                                   k, m, L), present_rows)

    async def close(self) -> None:
        self._closed = True
        if self._worker is not None:
            self._worker.cancel()
            await reap_task(self._worker, log, "ECCodec submit worker")
            self._worker = None
        err = RuntimeError("ECCodec closed")
        while not self._q.empty():
            _key, item = self._q.get_nowait()
            _set_exception_safe(item.future, err)
        self._pool.shutdown(wait=True, cancel_futures=True)

    # --- batching worker ---

    async def _submit(self, key: tuple, rows: np.ndarray) -> np.ndarray:
        if self._closed:
            raise RuntimeError("ECCodec closed")
        loop = asyncio.get_running_loop()
        if self._worker is None or self._worker.done():
            self._worker = loop.create_task(self._worker_loop())
        fut = loop.create_future()
        t0 = time.perf_counter() if tracing.sampling_on() else 0.0
        await self._q.put((key, _Pending(rows, fut, loop, t0)))
        return await fut

    async def _worker_loop(self) -> None:
        loop = asyncio.get_running_loop()
        batch: list[tuple[tuple, _Pending]] = []
        try:
            while True:
                batch = [await self._q.get()]
                # drain-then-sleep-then-drain, NEVER wait_for(q.get()):
                # on py<3.12 a timed-out wait_for can cancel Queue.get
                # AFTER it dequeued an item, silently dropping it — the
                # submitter's future then never resolves (rare hang under
                # the ckpt writer's submission rate)
                while len(batch) < self.max_batch:
                    try:
                        batch.append(self._q.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                if len(batch) < self.max_batch and self.max_wait_s > 0:
                    await asyncio.sleep(self.max_wait_s)
                    while len(batch) < self.max_batch:
                        try:
                            batch.append(self._q.get_nowait())
                        except asyncio.QueueEmpty:
                            break
                groups: dict[tuple, list[_Pending]] = {}
                for key, item in batch:
                    groups.setdefault(key, []).append(item)
                self.batches += len(groups)
                self.batched_items += len(batch)
                try:
                    await loop.run_in_executor(self._pool, self._flush,
                                               groups)
                except Exception as e:
                    log.exception("EC codec flush failed; failing batch")
                    for _key, item in batch:
                        item.loop.call_soon_threadsafe(
                            _set_exception_safe, item.future, e)
                batch = []
        except asyncio.CancelledError:
            err = RuntimeError("ECCodec closed")
            for _key, item in batch:
                _set_exception_safe(item.future, err)
            raise

    def _flush(self, groups: dict[tuple, list[_Pending]]) -> None:
        """Device work, runs on the codec thread: one kernel call per
        (shape-key) group covering every stacked request.  A launch that
        wins a head-sampling decision also finishes an `ec.flush` span:
        the codec thread's time in it (stacking, launch, result pull,
        per-row split), tagged with its rows' summed queue wait (submit
        to this flush starting)."""
        t_start = time.perf_counter()
        for key, items in groups.items():
            sampled = tracing.head_sample()
            t0 = time.perf_counter() if sampled else 0.0
            fn = self._fn(key)
            rows = items[0].rows
            n = pad_batch(len(items), self.max_batch, PAD_BASE)
            self.padded_rows += n - len(items)
            stacked = np.zeros((n,) + rows.shape, dtype=rows.dtype)
            for i, it in enumerate(items):
                stacked[i] = it.rows
            out = fn(stacked)
            for i, it in enumerate(items):
                # fused steps return a tuple of stacked arrays (shards,
                # crcs); each caller gets its row of every output
                res = (tuple(o[i] for o in out) if isinstance(out, tuple)
                       else np.asarray(out)[i])
                it.loop.call_soon_threadsafe(
                    _set_result_safe, it.future, res)
            if sampled:
                flush_s = time.perf_counter() - t0
                tracing.record_root(
                    "ec.flush", time.time() - flush_s, flush_s, op=key[0],
                    rows=len(items),
                    queue_s=sum(t_start - it.t0 for it in items if it.t0),
                    **self._span_tags.get(key, {}))

    # --- kernel selection + jit cache ---

    def _fn(self, key: tuple) -> Callable:
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax

        # on-disk executable cache: decode-kernel compiles are paid once
        # per checkout, not once per process (same rationale as the
        # checksum backend — a ~10 s Mosaic compile on the first degraded
        # read after a node loss is exactly what warmup_decode avoids)
        from t3fs.storage.codec_backend import enable_persistent_cache
        enable_persistent_cache()

        if self._interpret is None:
            # off the TPU, ship the XLA bit-matmul path — interpreted
            # Pallas is a correctness harness, not a data path.
            # T3FS_FORCE_PALLAS_INTERPRET=1 (suite) forces the Pallas
            # kernels under the interpreter for coverage.
            import os
            tpu = jax.devices()[0].platform == "tpu"
            force = os.environ.get("T3FS_FORCE_PALLAS_INTERPRET") == "1"
            self._interpret = not tpu and force
            self._use_pallas = tpu or force
        if key[0] == "enc":
            fn = self._build_encode(key)
        elif key[0] == "encv":
            fn = self._build_encode_verified(key)
        elif key[0] == "recv":
            fn = self._build_reconstruct_verified(key)
        elif key[0] == "rep":
            fn = self._build_repair(key)
        elif key[0] == "mencv":
            fn = self._build_msr_encode_verified(key)
        elif key[0] == "mrep":
            fn = self._build_msr_repair(key)
        elif key[0] == "mdecv":
            fn = self._build_msr_decode_verified(key)
        else:
            fn = self._build_reconstruct(key)
        self._fns[key] = fn
        return fn

    def _count(self, codec: str) -> None:
        self.codec_counts[codec] = self.codec_counts.get(codec, 0) + 1
        self.last_codec = codec

    def _word_tags(self, key: tuple, rs, rows) -> None:
        """Tag the key's `ec.flush` spans with the word program's cost."""
        from t3fs.ops.pallas_codec import gf_words_program
        self._span_tags[key]["gf_ops"] = gf_words_program(rs, rows).gf_ops

    def _build_encode(self, key: tuple) -> Callable:
        import jax

        from t3fs.ops import jax_codec

        _kind, k, m, L, code = key
        rs = rs_code(k, m, code)
        self._span_tags[key] = {"code": code}
        if self._use_pallas and L % 4 == 0:
            from t3fs.ops.pallas_codec import make_rs_encode_words_pallas
            W = L // 4
            bw = _pick_block(W, 16384)
            raw = jax.jit(make_rs_encode_words_pallas(
                rs, block_w=bw, interpret=self._interpret))
            self._word_tags(key, rs, rs.parity_rows)

            def encode_words(stacked: np.ndarray) -> np.ndarray:
                self._count("pallas-words")
                words = stacked.view(np.uint32).reshape(
                    stacked.shape[0], k, W)
                out = np.asarray(raw(words))
                return out.view(np.uint8).reshape(out.shape[0], m, L)
            return encode_words

        # off the TPU, or an odd length: XLA bit-matmul (also the oracle)
        raw = jax.jit(jax_codec.make_rs_encode(rs))

        def encode_xla(stacked: np.ndarray) -> np.ndarray:
            self._count("xla-bitmatmul")
            return np.asarray(raw(stacked))
        return encode_xla

    def _build_encode_verified(self, key: tuple) -> Callable:
        """Fused encode+CRC: one launch returns (parity, crcs) where crcs
        covers data shards then parity — the write-path twin of
        _build_reconstruct_verified.  Word-fused on 512-multiple chunks
        (bench.py's measured stripe step); otherwise an XLA-fused program
        (still one device round trip, still no CPU crc32c)."""
        _kind, k, m, L, code = key
        import jax

        rs = rs_code(k, m, code)
        self._span_tags[key] = {"code": code}
        if self._use_pallas and L % 512 == 0:
            from t3fs.ops.pallas_codec import make_stripe_encode_step_words
            step = jax.jit(make_stripe_encode_step_words(
                L // 4, k, m, interpret=self._interpret, code=code))
            self._word_tags(key, rs, rs.parity_rows)

            def encode_words(stacked: np.ndarray
                             ) -> tuple[np.ndarray, np.ndarray]:
                self._count("pallas-encode-words")
                words = stacked.view(np.uint32).reshape(
                    stacked.shape[0], k, L // 4)
                parity, crcs = step(words)
                parity = np.asarray(parity).view(np.uint8).reshape(
                    stacked.shape[0], m, L)
                return parity, np.asarray(crcs)
            return encode_words

        import jax.numpy as jnp

        from t3fs.ops import jax_codec

        encf = jax_codec.make_rs_encode(rs)
        crcf = jax_codec.make_crc32c_batch(L)

        @jax.jit
        def fused(stacked):
            parity = encf(stacked)
            n = stacked.shape[0]
            dcrc = crcf(stacked.reshape(n * k, L)).reshape(n, k)
            pcrc = crcf(parity.reshape(n * m, L)).reshape(n, m)
            return parity, jnp.concatenate([dcrc, pcrc], axis=1)

        def encode_xla(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count("xla-bitmatmul")
            parity, crcs = fused(stacked)
            return np.asarray(parity), np.asarray(crcs)
        return encode_xla

    def _build_reconstruct(self, key: tuple) -> Callable:
        import jax

        from t3fs.ops import jax_codec

        _kind, present, want, k, m, L, code = key
        rs = rs_code(k, m, code)
        self._span_tags[key] = {"code": code}
        if self._use_pallas and L % 4 == 0:
            # decode stays word-packed: the GF(2^8) decode constants run as
            # a scheduled SWAR xtimes/xor program at encode-class rates
            from t3fs.ops.pallas_codec import make_rs_reconstruct_words_pallas
            W = L // 4
            bw = _pick_block(W, 16384)
            raw = jax.jit(make_rs_reconstruct_words_pallas(
                present, want, rs, block_w=bw, interpret=self._interpret))
            self._word_tags(key, rs, rs.reconstruct_gfmatrix(
                list(present), list(want)))
            nwant = len(want)

            def reconstruct_words(stacked: np.ndarray) -> np.ndarray:
                self._count("pallas-rec-words")
                words = stacked.view(np.uint32).reshape(
                    stacked.shape[0], k, W)
                out = np.asarray(raw(words))
                return out.view(np.uint8).reshape(out.shape[0], nwant, L)
            return reconstruct_words

        # off the TPU, or an odd length: XLA bit-matmul (also the oracle)
        raw = jax.jit(jax_codec.make_rs_reconstruct(present, want, rs))

        def reconstruct_xla(stacked: np.ndarray) -> np.ndarray:
            self._count("xla-bitmatmul")
            return np.asarray(raw(stacked))
        return reconstruct_xla

    def _build_reconstruct_verified(self, key: tuple) -> Callable:
        """Fused decode+verify: one launch returns (rebuilt, crcs) where
        crcs covers survivors + rebuilt shards.  Word-fused on 512-multiple
        chunks; otherwise an XLA-fused program (still one device round
        trip, still no CPU crc32c)."""
        _kind, present, want, k, m, L, code = key
        import jax

        rs = rs_code(k, m, code)
        self._span_tags[key] = {"code": code}
        nwant = len(want)
        if self._use_pallas and L % 512 == 0:
            from t3fs.ops.pallas_codec import make_stripe_decode_step_words
            step = jax.jit(make_stripe_decode_step_words(
                L // 4, present, want, k, m, interpret=self._interpret,
                code=code))
            self._word_tags(key, rs, rs.reconstruct_gfmatrix(
                list(present), list(want)))

            def decode_words(stacked: np.ndarray
                             ) -> tuple[np.ndarray, np.ndarray]:
                self._count("pallas-decode-words")
                words = stacked.view(np.uint32).reshape(
                    stacked.shape[0], k, L // 4)
                rebuilt, crcs = step(words)
                rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(
                    stacked.shape[0], nwant, L)
                return rebuilt, np.asarray(crcs)
            return decode_words

        import jax.numpy as jnp

        from t3fs.ops import jax_codec

        recf = jax_codec.make_rs_reconstruct(present, want, rs)
        crcf = jax_codec.make_crc32c_batch(L)

        @jax.jit
        def fused(stacked):
            rebuilt = recf(stacked)
            n = stacked.shape[0]
            scrc = crcf(stacked.reshape(n * k, L)).reshape(n, k)
            rcrc = crcf(rebuilt.reshape(n * nwant, L)).reshape(n, nwant)
            return rebuilt, jnp.concatenate([scrc, rcrc], axis=1)

        def decode_xla(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count("xla-bitmatmul")
            rebuilt, crcs = fused(stacked)
            return np.asarray(rebuilt), np.asarray(crcs)
        return decode_xla

    def _build_repair(self, key: tuple) -> Callable:
        """Scheduled single-row repair + CRC of the rebuilt bytes.  Pallas
        word kernel on 512-multiple lengths (the fused repair step);
        otherwise the SAME schedule as a plain-jnp word program — identical
        op structure, so CPU fabrics and odd tail lengths share one code
        path with the device kernel."""
        _kind, coeffs, k, m, L = key
        import jax

        from t3fs.ops.repair_program import schedule_repair_program

        prog = schedule_repair_program(coeffs)
        h = prog.num_helpers
        if self._use_pallas and L % 512 == 0:
            from t3fs.ops.pallas_codec import make_repair_step_words
            step = jax.jit(make_repair_step_words(
                L // 4, prog, interpret=self._interpret))

            def repair_words(stacked: np.ndarray
                             ) -> tuple[np.ndarray, np.ndarray]:
                self._count("pallas-repair-words")
                words = stacked.view(np.uint32).reshape(
                    stacked.shape[0], h, L // 4)
                rebuilt, crcs = step(words)
                rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(
                    stacked.shape[0], L)
                return rebuilt, np.asarray(crcs)
            return repair_words

        from t3fs.ops.jax_codec import crc32c_batch_jit
        from t3fs.ops.pallas_codec import _poly_shifts, _xtimes_u32

        # every RS code of one (k, m) shares the field
        shifts = _poly_shifts(rs_code(k, m, stored_code(k, m)))
        planes = prog.planes
        top = len(planes) - 1
        pad = (-L) % 4
        Wp = (L + pad) // 4

        @jax.jit
        def run(words):                          # (n, h, Wp) -> (n, Wp)
            acc = None
            for i in planes[top]:
                acc = words[:, i] if acc is None else acc ^ words[:, i]
            for b in range(top - 1, -1, -1):
                acc = _xtimes_u32(acc, shifts)
                for i in planes[b]:
                    acc = acc ^ words[:, i]
            return acc

        crcf = crc32c_batch_jit(L)

        def repair_xla(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count("xla-repair-words")
            n = stacked.shape[0]
            rows = (np.pad(stacked, ((0, 0), (0, 0), (0, pad))) if pad
                    else stacked)
            words = np.ascontiguousarray(rows).view(np.uint32).reshape(
                n, h, Wp)
            out = np.asarray(run(words)).view(np.uint8).reshape(n, -1)[:, :L]
            out = np.ascontiguousarray(out)
            return out, np.asarray(crcf(out))
        return repair_xla

    def _build_msr_encode_verified(self, key: tuple) -> Callable:
        _kind, k, m, L = key
        from t3fs.ops.msr import default_msr
        from t3fs.ops.msr_codec import make_msr_encode_step

        step = make_msr_encode_step(default_msr(k, m), L,
                                    interpret=bool(self._interpret),
                                    use_pallas=bool(self._use_pallas))
        codec = ("pallas-msr-encode" if self._use_pallas and L % 512 == 0
                 else "xla-msr-encode")

        def encode(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count(codec)
            return step(stacked)
        return encode

    def _build_msr_repair(self, key: tuple) -> Callable:
        """The pm-msr projection rebuild: stage A/C constant folds around
        the two scheduled stage-B repair programs, one launch producing
        the WHOLE rebuilt chunk plus its fused CRC32C.  Pallas word
        kernels on 512-multiple sub-chunks; otherwise the identical
        schedule as plain-jnp byte SWAR (the odd-length XLA fallback)."""
        _kind, failed_slot, k, m, L = key
        from t3fs.ops.msr import default_msr
        from t3fs.ops.msr_codec import make_msr_repair_step

        code = default_msr(k, m)
        step = make_msr_repair_step(code, failed_slot, L,
                                    interpret=bool(self._interpret),
                                    use_pallas=bool(self._use_pallas))
        sub = L // code.alpha
        codec = ("pallas-msr-repair" if self._use_pallas and sub % 512 == 0
                 else "xla-msr-repair")

        def repair(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count(codec)
            return step(stacked)
        return repair

    def _build_msr_decode_verified(self, key: tuple) -> Callable:
        _kind, present, want, k, m, L = key
        from t3fs.ops.msr import default_msr
        from t3fs.ops.msr_codec import make_msr_decode_step

        step = make_msr_decode_step(default_msr(k, m), present, want, L)

        def decode(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            self._count("xla-msr-decode")
            return step(stacked)
        return decode

    # --- warmup (DeviceChecksumBackend.warmup analog) ---

    def _warm(self, jobs: list[tuple[tuple, tuple[int, ...]]],
              batch_sizes: tuple[int, ...] | None) -> int:
        """Run each (key, per-request shape) once per batch size (default:
        every padded size pad_batch can produce) on the codec thread, each
        as its own job so close() (shutdown with cancel_futures) drops
        whatever hasn't started.  A failed compile raises: the same key
        would fail on the serving path.  Returns the number of programs
        run."""
        from concurrent.futures import CancelledError

        sizes = batch_ladder(self.max_batch, PAD_BASE) \
            if batch_sizes is None else \
            sorted({pad_batch(n, self.max_batch, PAD_BASE)
                    for n in batch_sizes})

        def one(key: tuple, shape: tuple[int, ...]) -> None:
            if not self._closed:
                self._fn(key)(np.zeros(shape, dtype=np.uint8))

        futs = []
        for key, shape in jobs:
            for nb in sizes:
                if self._closed:
                    break
                try:
                    futs.append(self._pool.submit(one, key, (nb,) + shape))
                except RuntimeError:   # pool already shut down
                    break
        for f in futs:
            try:
                f.result()
            except CancelledError:
                break
        return len(futs)

    def warmup_decode(self, patterns: list[tuple[tuple[int, ...],
                                                 tuple[int, ...]]],
                      L: int, k: int = 8, m: int = 2,
                      batch_sizes: tuple[int, ...] | None = None,
                      code: str | None = None) -> int:
        """Precompile the hot (present, want, L) fused decode steps
        off-path — call at server start / when a node loss is detected, so
        the FIRST degraded read doesn't eat a multi-second Mosaic compile
        on the read path."""
        code = _code(k, m, code)
        return self._warm([(("recv", tuple(present), tuple(want), k, m, L,
                             code), (k, L)) for present, want in patterns],
                          batch_sizes)

    def warmup_encode(self, L: int, k: int = 8, m: int = 2,
                      batch_sizes: tuple[int, ...] | None = None,
                      code: str | None = None) -> int:
        """Precompile the fused encode+CRC step at chunk length L (the
        checkpoint writer's stripe encode)."""
        return self._warm([(("encv", k, m, L, _code(k, m, code)), (k, L))],
                          batch_sizes)

    def warmup_repair(self, coeff_rows: list[tuple[int, ...]], L: int,
                      k: int = 8, m: int = 2,
                      batch_sizes: tuple[int, ...] | None = None) -> int:
        """Precompile the hot repair programs off-path — the repair twin of
        warmup_decode, called from RepairDriver setup so the FIRST drill
        iteration doesn't eat a Mosaic compile mid-rebuild.  coeff_rows are
        the per-program coefficient tuples (e.g. the all-ones local-group
        programs plus the decode rows the scrub plan will actually run)."""
        jobs = []
        for coeffs in coeff_rows:
            coeffs = tuple(int(c) for c in coeffs)
            jobs.append((("rep", coeffs, k, m, L), (len(coeffs), L)))
        return self._warm(jobs, batch_sizes)

    def warmup_msr(self, slots: list[int], L: int, k: int = 8, m: int = 2,
                   batch_sizes: tuple[int, ...] | None = None) -> int:
        """Precompile the pm-msr projection-repair step for each failed
        slot (plus the coupled encode) — warmup_repair's pm-msr twin; the
        repair kernels are per-failed-slot, so a node loss warms exactly
        the programs the scrub plan will run."""
        from t3fs.ops.msr import default_msr

        code = default_msr(k, m)
        jobs = [(("mencv", k, m, L), (k, L))]
        jobs += [(("mrep", int(f), k, m, L), (code.d, L // 2))
                 for f in slots]
        return self._warm(jobs, batch_sizes)
