"""Share of the device's program time spent in admission: the prefill and
graft programs over all programs run in the traced span."""
import programs


def read(ctx):
    red = ctx.reduction
    total = sum(red.module_s.values()) if red is not None else 0.0
    if total <= 0:
        return None
    return 100.0 * (programs.seconds(red, "prefill") + programs.seconds(red, "graft")) / total
