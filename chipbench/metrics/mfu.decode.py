"""Model FLOPs of the decoded tokens in the traced span (each over its own
context) over (decode device time * the chip's bf16 peak)."""
import counts
import programs


def read(ctx):
    red = ctx.reduction
    t = programs.seconds(red, "decode") if red is not None else 0.0
    flops = sum(counts.decode_flops(ctx.model, p + j) for p, j in ctx.traced_tokens() if j >= 1)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
