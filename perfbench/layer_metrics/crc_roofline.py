"""Share of the HBM roofline of the device CRC program that serves the
storage nodes' DeviceChecksumBackend launches (make_crc32c_words_raw),
over the traced window: the bytes the CRCs of the window's real rows need
(perfbench.work.crc32c_bytes, one full chunk per row) at the chip's HBM
peak, over the program's device time in the trace."""

from perfbench import work

PROGRAM = r"^jit_raw_crc$"


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    rows = r.trace_counters.get("crc_rows", 0)
    return work.roofline_share(rows * work.crc32c_bytes(r.config["chunk_size"]),
                               r.peaks["hbm_bytes_per_s"],
                               r.trace.program_seconds(PROGRAM))
