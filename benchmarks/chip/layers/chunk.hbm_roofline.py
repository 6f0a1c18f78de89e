"""Chunk program: share of the memory-bandwidth roofline. The least time
the bytes any expansion has to move (``roofline.expansion_bytes``, from
the graph and the reference) take at the chip's HBM bandwidth, over the
chunk program's device time (``chunk.device_s``); both per job."""


def read(ctx):
    t = ctx.metric("chunk.device_s")
    bw = ctx.peaks.get("hbm_bytes_per_s")
    if not t or not bw:
        return None
    return 100.0 * (ctx.min_bytes / bw) / t
