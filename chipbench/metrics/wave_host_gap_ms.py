"""Median device idle between one decode wave and the next, over the waves
the host handed straight on (no admission between), in the traced span: the
host's turnaround per wave. Waves are the engine's ``engine.pick`` spans
(``engine_spans.wave_host_gaps``); a trace without them reads nothing."""
import statistics

import engine_spans


def read(ctx):
    trace = engine_spans.for_cell(ctx.cell["name"])
    gaps = engine_spans.wave_host_gaps(trace) if trace is not None else []
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
