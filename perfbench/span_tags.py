"""What the program's spans carry beyond the server spans' wire, queue
and apply tags, for the metric readers: per-hop tags (send_s, frame_s,
crc_s, engine_s, crc_queue_s, crc_result_s) and the local spans that are
traces of their own: `loop.wait` (t3fs/utils/loop_meter.py, tagged with
the loop meter's running totals) and `ec.flush` (one per ECCodec launch).
A run of a program without them reads nothing, and each reader then
returns None."""

from __future__ import annotations

from perfbench.readings import WRITE_SPANS, mean_ms

WRITE_CLIENT_SPANS = tuple("rpc." + n for n in WRITE_SPANS)
ENCODE_OPS = ("enc", "encv", "mencv")
DECODE_OPS = ("rec", "recv", "mdecv")


def tag_mean_ms(spans: list[dict], tag: str) -> float | None:
    """Mean of `tag` over the spans that carry it, in ms."""
    return mean_ms([s["tags"][tag] for s in spans if tag in s["tags"]])


def client_spans(r, names: tuple[str, ...]) -> list[dict]:
    return [s for s in r.spans
            if s.get("kind") == "client" and s["name"] in names]


def local_spans(r, name: str) -> list[dict]:
    return [s for s in r.spans
            if s.get("kind") == "local" and s["name"] == name]


def ec_flushes(r, ops: tuple[str, ...]) -> list[dict]:
    return [s for s in local_spans(r, "ec.flush")
            if s["tags"].get("op") in ops]


def ec_queue_ms(r, ops: tuple[str, ...]) -> float | None:
    """Mean wait of a stripe from submit to its flush, in ms."""
    f = ec_flushes(r, ops)
    rows = sum(s["tags"]["rows"] for s in f)
    return 1e3 * sum(s["tags"]["queue_s"] for s in f) / rows if rows else None


def ec_flush_ms(r, ops: tuple[str, ...]) -> float | None:
    """Mean time the codec thread spends on one launch, in ms."""
    return mean_ms([s["dur_s"] for s in ec_flushes(r, ops)])


def loop_busy_share(r) -> float | None:
    """Per cent of the loop's wall time outside select(), between the
    window's first and last `loop.wait` span: the difference of the
    meter's running totals they carry."""
    waits = sorted(local_spans(r, "loop.wait"),
                   key=lambda s: s["tags"]["iterations"])
    if len(waits) < 2:
        return None
    a, b = waits[0]["tags"], waits[-1]["tags"]
    busy, wait = b["busy_s"] - a["busy_s"], b["wait_s"] - a["wait_s"]
    return 100.0 * busy / (busy + wait) if busy + wait > 0 else None
