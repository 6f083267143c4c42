"""score_fuse's share of its roofline (%): the least time the chip could
take for the work the window's stage-2 calls needed (the larger of the
operations over peak FLOP/s and the bytes over peak HBM bandwidth, from
``bench/counts/score_fuse.py``), over the device time of the kernel's ops
in the trace."""
import numpy as np

from harness import tracefile

KERNEL = "fused_score_select_pallas"


def read(ctx):
    if ctx.trace is None or not ctx.calls:
        return None
    secs, n_ops = tracefile.kernel_time(ctx.trace, KERNEL)
    if n_ops == 0 or secs <= 0.0:
        return None
    counts, peaks = ctx.counts("score_fuse"), ctx.peaks()
    ref = ctx.reference
    vocab = ref.tools.n_terms
    top_s = int(ctx.cell.config["routing"]["top_s"])
    t_flops = t_bytes = 0.0
    for texts in ctx.calls:
        rows = [ref.tool_rows(q) for q in texts]
        t_flops += counts.flops([len(r) for r in rows], vocab) / peaks["bf16_flops_per_s"]
        t_bytes += counts.bytes_accessed(
            len(texts), len(np.unique(np.concatenate(rows))), vocab, top_s,
        ) / peaks["hbm_bytes_per_s"]
    bound = "HBM bandwidth" if t_bytes >= t_flops else "bf16 FLOP/s"
    least = max(t_flops, t_bytes) / len(ctx.calls) * n_ops
    ctx.note(f"score_fuse: {n_ops} calls, {secs:.6f} s on the device, "
             f"{len(ctx.calls)} calls judged; bound by {bound}")
    return 100.0 * least / secs
