"""One run of one cell: set-up, the measured window, the check, the
readings.  run.py calls `run_cell` once it has found the chip; the tests
call it on the CPU, with the codecs under the Pallas interpreter.

Set-up, in order (all of it counts in setup_s): the data from --seed;
warm-up of the codec programs the cell runs, from the compile cache in the
checkout; the cluster, whose EC layout has to store the parity code the
configuration names; the fill of what the traffic reads; the mix's fault;
gc.freeze().  Then the window, timed by the host clock; with --trace 1 a
JAX profiler window of a few seconds sits in its middle and every program
span is exported.  After the window: the device's peak memory, a read-back
of every stored copy the writes left, the cluster's stop, and only then
the comparison with the reference and the profile's reduction.
"""

from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import shutil
import sys
import time
from collections import Counter

from perfbench import trace_reduce, verify, work
from perfbench.data import Payloads, rng_for
from perfbench.hostmon import CompileLog, LoopLag, StallProbe
from perfbench.readings import Readings, load_reader
from perfbench.spec import Cell
from perfbench.sut import Deployment
from perfbench.traffic import Load, load_mix, resolve

PROFILE_S = 3.0          # profiler window, at most a third of the run


def log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def _drain_spans() -> list[dict]:
    from t3fs.utils import tracing

    out = []
    while True:
        part = tracing.BUFFER.drain(1 << 16)
        if not part:
            return out
        out += part


def _counters(dep: Deployment) -> dict[str, int]:
    return {**dep.crc_counters(), **dep.ec_counters()}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b}


def _checked_objects(book, sample: int, rng) -> list[int]:
    """The acknowledged objects whose copies the check reads back: all of
    them, or `sample` drawn from the seed."""
    acked = sorted(book.acked)
    if sample and len(acked) > sample:
        acked = sorted(int(o) for o in rng.choice(acked, sample,
                                                  replace=False))
    return acked


def _peak_memory() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: str, t_process: float, control: str | None = None,
             on_cpu: bool = False, config_overrides: dict | None = None
             ) -> dict:
    return asyncio.run(_run(cell, seed, seconds, trace, root=root,
                            t_process=t_process, control=control,
                            on_cpu=on_cpu,
                            config_overrides=config_overrides or {}))


async def _run(cell: Cell, seed: int, seconds: float, trace: bool, *,
               root: str, t_process: float, control: str | None,
               on_cpu: bool, config_overrides: dict) -> dict:
    import jax

    from t3fs.storage.codec_backend import (DeviceChecksumBackend,
                                            make_checksum_backend)
    from t3fs.storage.server import WARMUP_PAYLOAD_SIZES
    from t3fs.utils.tracing import TraceConfig

    cfg = {**cell.config, **config_overrides}
    mix = load_mix(cell.mix_path)
    ec = cfg["layout"] == "ec"
    code = verify.ec_code(cfg) if ec else None
    cs = cfg["chunk_size"]
    phases: dict[str, float] = {}
    compiles = CompileLog()

    def phase(name: str, t0: float) -> float:
        t = time.perf_counter()
        phases[name] = t - t0
        return t

    # --- data, from the seed, before anything else holds the loop ---
    t = time.perf_counter()
    obj_bytes = cfg["k"] * cs if ec else cs
    resolve(mix, cfg["working_set_bytes"], obj_bytes)
    payloads = Payloads(seed, obj_bytes, cs)
    t = phase("data", t)

    # --- warm-up: the codec programs this cell runs ---
    backend = ((lambda: DeviceChecksumBackend(max_batch=4,
                                              interpret_on_cpu=True))
               if on_cpu else "tpu")
    crc = make_checksum_backend(backend)
    try:
        # the storage nodes warm WARMUP_PAYLOAD_SIZES at start whatever the
        # traffic: warming them here keeps those compiles out of the window
        n_warm = crc.warmup(sorted(set(WARMUP_PAYLOAD_SIZES) | {cs}))
    finally:
        await crc.close()
    codec = None
    if ec:
        from t3fs.client.ec_codec import ECCodec

        k, m = cfg["k"], cfg["m"]
        codec = ECCodec()
        # at most one stripe in flight per client: batches of 1..clients
        writers = [g.clients for g in mix.groups if g.op == "write" or g.fill]
        if writers:
            n_warm += codec.warmup_encode(
                cs, k, m, batch_sizes=tuple(range(1, max(writers) + 1)))
        lost = tuple(mix.fault.get("kill_data_slots", ()))
        readers = [g.clients for g in mix.groups if g.op == "read"]
        if lost and readers:
            present = tuple(s for s in range(k + m) if s not in lost)[:k]
            n_warm += codec.warmup_decode(
                [(present, lost)], cs, k, m,
                batch_sizes=tuple(range(1, max(readers) + 1)))
    p, s, h = compiles.snapshot()
    log(f"warm-up: {n_warm} programs run, {p} jit programs built ({h} from "
        f"the persistent cache) in {s:.3f} s of compile")
    t = phase("warmup", t)
    gc.collect()
    gc.freeze()

    # --- the cluster, the fill, the fault ---
    trace_cfg = (TraceConfig(sample_rate=1.0, export="all",
                             max_spans=1 << 20, export_max=1 << 22)
                 if trace else None)
    dep = Deployment(cfg, backend, codec, trace=trace_cfg, control=control)
    lag = LoopLag()
    probe = StallProbe(lag)
    prof_dir = os.path.join(root, ".perfbench_trace")
    prof: dict = {}
    try:
        await dep.start()
        if ec and dep.ec_layout.code_id != code:
            # before the window: no run is judged against another
            # code's parity
            raise ValueError(
                f"the layout stores parity of code {dep.ec_layout.code_id!r}"
                f", the configuration names {code!r}")
        t = phase("cluster", t)
        load = Load(mix, dep, payloads, seed)
        await load.fill()
        t = phase("fill", t)
        if mix.fault.get("kill_data_slots"):
            victims = await dep.kill_data_slots(mix.fault["kill_data_slots"])
            log(f"fault: nodes {victims} fail-stopped")
            t = phase("fault", t)
        gc.collect()
        gc.freeze()
        lag.start()
        probe.start()
        if trace:
            _drain_spans()              # set-up's spans are not the window's
            shutil.rmtree(prof_dir, ignore_errors=True)

        # --- the measured window ---
        c0, k0 = compiles.snapshot(), _counters(dep)
        t_start = time.perf_counter()
        setup_s = t_start - t_process
        epoch0 = time.time()
        lag.recording = True
        prof_task = (asyncio.ensure_future(_profile(
            dep, prof_dir, t_start, seconds, prof)) if trace else None)
        window_s = await load.run(t_start, seconds)
        lag.recording = False
        if prof_task is not None:
            await prof_task
        c1, k1 = compiles.snapshot(), _counters(dep)
        epoch1 = epoch0 + window_s
        memory_peak = _peak_memory()
        spans = ([s for s in _drain_spans() if epoch0 <= s["t0"] <= epoch1]
                 if trace else [])

        # --- read back every copy the writes left, then stop ---
        t = time.perf_counter()
        copies = {g.index: await dep.copies(g.index, _checked_objects(
            load.books[g.index], g.sample, rng_for(seed, 4, g.index)))
            for g in mix.groups if g.op == "write"}
        phases["read_back"] = time.perf_counter() - t
    finally:
        await lag.stop()
        probe.stop()
        try:
            await dep.stop()
        finally:
            gc.unfreeze()
            if prof.get("started"):
                jax.profiler.stop_trace()

    # --- the comparison with the reference ---
    t = time.perf_counter()
    counts = Counter()
    for g in mix.groups:
        book = load.books[g.index]
        if g.op == "write":
            counts += verify.check_copies(cfg, copies[g.index], book, payloads)
        else:
            counts += verify.check_answers(
                cfg, load.samples[g.index].items, book, payloads)
    stats = load.stats
    attempted = sum(s.attempted for s in stats.values())
    failed = sum(s.failed for s in stats.values())
    counts["requests_failed"] = failed
    correct, checks = verify.judge(counts)
    kinds = {g.op for g in mix.groups}
    checks = {n: c for n, c in checks.items()
              if n == "requests_failed"
              or (n.startswith("copies") and "write" in kinds)
              or (n.startswith("answers") and "read" in kinds)}
    phases["check"] = time.perf_counter() - t

    # --- the readings ---
    summary = None
    if trace and prof.get("started"):
        summary = _reduce(prof_dir)
    dev = jax.devices()[0]
    peaks = work.peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    r = Readings(config=cfg, groups=mix.groups, fault=mix.fault, stats=stats,
                 window_s=window_s, setup_s=setup_s, loop_lag=lag.samples,
                 counters=_delta(k0, k1), spans=spans, trace=summary,
                 trace_counters=prof.get("counters", {}), peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(cell.reader_path(m, trace))(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": trace_reduce.label_gaps(summary, spans,
                                                 prof["epoch_lo"])}

    # --- what the run did, then the numbers compared, last ---
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in phases.items())
        + f"; setup_s {setup_s:.3f}")
    log(f"compiles in the window: {c1[0] - c0[0] - (c1[2] - c0[2])} "
        f"(cache hits {c1[2] - c0[2]})")
    for g in mix.groups:
        st = stats[g.index]
        lat = sorted(st.latencies)
        if lat:
            log(f"group {g.index} {g.op}: {st.attempted} requests, "
                f"{st.failed} failed {dict(st.errors)}, "
                f"p50 {1e3 * lat[len(lat) // 2]:.3f} ms, "
                f"max {1e3 * lat[-1]:.3f} ms")
    if lag.samples:
        log(f"loop lag: max {1e3 * max(lag.samples):.3f} ms over "
            f"{len(lag.samples)} ticks")
    for st in probe.stalls:
        log(f"loop stall: {json.dumps(st)}")
    if codec is not None:
        log(f"ECCodec implementations, set-up and window: "
            f"{codec.codec_counts}")
    log(f"window {window_s:.3f} s; codec counters {_delta(k0, k1)}; "
        f"compared: {counts.get('copies_checked', 0)} copies, "
        f"{counts.get('answers_checked', 0)} answers")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


async def _profile(dep: Deployment, prof_dir: str, t_start: float,
                   seconds: float, out: dict) -> None:
    """A profiler window of PROFILE_S (at most a third of the run) in the
    middle of the measured window, marked on the loop thread so the
    reduction can cut it out of the trace; the codec counters are read at
    its edges."""
    import jax

    dur = min(PROFILE_S, seconds / 3)
    await asyncio.sleep(max(0.0, t_start + (seconds - dur) / 2
                            - time.perf_counter()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    await asyncio.to_thread(jax.profiler.start_trace, prof_dir,
                            profiler_options=opts)
    out["started"] = True
    mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK)
    k0 = _counters(dep)
    mark.__enter__()
    out["epoch_lo"] = time.time()
    await asyncio.sleep(dur)
    mark.__exit__(None, None, None)
    out["counters"] = _delta(k0, _counters(dep))


def _reduce(prof_dir: str):
    try:
        paths = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        return trace_reduce.reduce(trace_reduce.load(paths[0]))
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)
