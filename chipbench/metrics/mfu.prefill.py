"""Model FLOPs of the prefills in the traced span (counted from their
prompt lengths) over (prefill device time * the chip's bf16 peak)."""
import counts
import programs


def read(ctx):
    red = ctx.reduction
    t = programs.seconds(red, "prefill") if red is not None else 0.0
    flops = sum(counts.prefill_flops(ctx.model, p) for p, j in ctx.traced_tokens() if j == 0)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
