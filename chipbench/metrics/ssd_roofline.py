"""Roofline share of the SSD kernel: the least time its calls in the traced
span could take (per call the larger of FLOPs / peak and bytes / HBM
bandwidth, one call per layer per prefill) over the summed device time of
the kernel."""
import counts
import programs


def read(ctx):
    red = ctx.reduction
    t = red.ops_matching(programs.KERNELS["ssd"]) if red is not None else 0.0
    m = ctx.model
    bound = sum(m["n_layers"] * counts.roofline_s(counts.ssd_flops(m, p),
                                                  counts.ssd_bytes(m, p), ctx.peaks)
                for p, j in ctx.traced_tokens() if j == 0)
    if t <= 0 or bound <= 0:
        return None
    return 100.0 * bound / t
