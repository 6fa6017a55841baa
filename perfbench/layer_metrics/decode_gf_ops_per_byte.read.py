"""32-bit VPU operations the GF(2^8) part of the device decode issues per
survivor byte: the mean, over the window's decode `ec.flush` spans, of the
`gf_ops` tag (the scheduled ops per word position of the baked word
program: XORs plus each xtimes step's own ops) over the 4k survivor bytes
of a word position.  A program without the tag reads nothing."""

from perfbench.span_tags import DECODE_OPS, ec_flushes


def read(r):
    ops = [s["tags"]["gf_ops"] for s in ec_flushes(r, DECODE_OPS)
           if "gf_ops" in s["tags"]]
    return sum(ops) / len(ops) / (4 * r.config["k"]) if ops else None
