"""Share of the HBM roofline of the fused decode+verify program
(ECCodec key `recv`, make_stripe_decode_step_words) over the traced
window: the bytes the window's real stripes need
(perfbench.work.decode_bytes, as many shards rebuilt as the mix kills) at
the chip's HBM peak, over the program's device time in the trace."""

from perfbench import work

PROGRAM = r"^jit_step$"


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    c = r.config
    rows = r.trace_counters.get("ec_rows", 0)
    lost = len(r.fault.get("kill_data_slots", ()))
    return work.roofline_share(
        rows * work.decode_bytes(c["k"], lost, c["chunk_size"]),
        r.peaks["hbm_bytes_per_s"], r.trace.program_seconds(PROGRAM))
