"""Share of the HBM roofline of the fused RS encode+CRC program
(ECCodec key `encv`, make_stripe_encode_step_words) over the traced
window: the bytes the window's real stripes need
(perfbench.work.encode_bytes) at the chip's HBM peak, over the program's
device time in the trace."""

from perfbench import work

PROGRAM = r"^jit_step$"


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    c = r.config
    rows = r.trace_counters.get("ec_rows", 0)
    return work.roofline_share(
        rows * work.encode_bytes(c["k"], c["m"], c["chunk_size"]),
        r.peaks["hbm_bytes_per_s"], r.trace.program_seconds(PROGRAM))
