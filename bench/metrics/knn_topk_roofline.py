"""The brute lane kernel's share of its roofline, in percent: the least
time the device needs for the exact work of the window's brute-lane rows
(each row against every corpus row, ``work.scan_topk`` per call) over the
device time of the kernel's ops in the trace.  Nothing to read when the
kernel did not run in the window."""
import work

PATTERN = r"^%knn_tile_topk\b"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(PATTERN)
    flops = nbytes = 0
    for c in ctx.calls:
        rows = int((c.source == 2).sum())
        if rows:
            f, b = work.scan_topk(rows, ctx.n_corpus, ctx.dim, ctx.k)
            flops, nbytes = flops + f, nbytes + b
    if kernel_s <= 0 or not flops:
        return None
    t_min, _ = work.roofline_s(flops, nbytes, work.device_peak(ctx.device_kind))
    return 100.0 * t_min / kernel_s
