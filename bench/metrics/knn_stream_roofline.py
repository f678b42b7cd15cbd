"""The dense engine's kernel (``knn_stream_topk_prefetch``) share of its
roofline, in percent: the least time the device needs for the exact work
of the window's dense-engine rows over the device time of the kernel's
ops in the trace.

The work is what the program counted on the device (``stats.dense_pairs``
and ``stats.dense_cand_rows``, summed over the window's calls): 2·D
operations per query–candidate pair the kernel scored, each tile's real
rows against the union of their 3^m cell ranges; bytes of each candidate
row a tile read and each dense row read once (float32), plus k float32
distances and k int32 ids out per dense row.  On a TPU v5e the kernel's
ops are named ``%knn_stream_topk_prefetch.<n>`` (a ``tpu_custom_call``
in ``jit_dense_join_jit``).  Nothing to read when no dense row ran, or
when the program counts no pairs."""
import work

PATTERN = r"^%knn_stream_topk_prefetch\b"


def read(ctx):
    if ctx.trace is None:
        return None
    stats = [c.stats for c in ctx.calls if hasattr(c.stats, "dense_pairs")]
    pairs = sum(s.dense_pairs for s in stats)
    cand_rows = sum(s.dense_cand_rows for s in stats)
    rows = sum(sum(s.batch_sizes) for s in stats)
    kernel_s = ctx.trace.kernel_s(PATTERN)
    if kernel_s <= 0 or not pairs:
        return None
    flops = 2 * ctx.dim * pairs
    nbytes = (work.F32 * ctx.dim * (cand_rows + rows)
              + 2 * work.F32 * ctx.k * rows)
    peak = work.device_peak(ctx.device_kind)
    t_min, _ = work.roofline_s(flops, nbytes, peak)
    return 100.0 * t_min / kernel_s
