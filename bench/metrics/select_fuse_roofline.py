"""select_fuse's share of its roofline (%): the least time the chip could
take for the work the window's engine calls needed (the larger of the
operations over peak FLOP/s and the bytes over peak HBM bandwidth, from
``bench/counts/select_fuse.py``), over the device time of the kernel's
ops in the trace."""
from harness import tracefile

KERNEL = "fused_select_pallas"


def read(ctx):
    if ctx.trace is None or not ctx.calls:
        return None
    secs, n_ops = tracefile.kernel_time(ctx.trace, KERNEL)
    if n_ops == 0 or secs <= 0.0:
        return None
    ref = ctx.reference
    k_slot = getattr(ref, "k_slot", None)
    if k_slot is None:
        return None
    counts, peaks = ctx.counts("select_fuse"), ctx.peaks()
    routing = ctx.cell.config["routing"]
    width = min(int(routing["top_s"]), ref.n) * k_slot
    k = min(int(routing["top_k"]), width)
    t_flops = t_bytes = 0.0
    for texts in ctx.calls:
        t_flops += counts.flops(len(texts), width, k) / peaks["bf16_flops_per_s"]
        t_bytes += counts.bytes_accessed(len(texts), width) / peaks["hbm_bytes_per_s"]
    bound = "HBM bandwidth" if t_bytes >= t_flops else "bf16 FLOP/s"
    least = max(t_flops, t_bytes) / len(ctx.calls) * n_ops
    ctx.note(f"select_fuse: {n_ops} calls, {secs:.6f} s on the device, "
             f"{len(ctx.calls)} calls judged; bound by {bound}")
    return 100.0 * least / secs
