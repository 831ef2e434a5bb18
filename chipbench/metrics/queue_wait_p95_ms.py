"""Nearest-rank p95, over the window's requests, of the engine's stamps
``admitted_at - queued_at``: how long a request waited for a slot. One never
admitted counts as infinite; requests without the stamps read nothing."""
import harness


def read(ctx):
    waits = []
    for r in harness.in_window(ctx.window):
        queued = getattr(r.req, "queued_at", None)
        if queued is None:
            return None
        admitted = getattr(r.req, "admitted_at", None)
        waits.append(float("inf") if admitted is None else admitted - queued)
    if not waits:
        return None
    return 1e3 * harness.nearest_rank(waits, 95)
