"""How the program's compiled programs and kernels appear in a device trace.

The engine jits ``functools.partial(prefill)`` and ``functools.partial(
decode_step)``, which the trace names ``jit__unknown(<fingerprint>)``: one
program per prompt length for prefill, one for the slot batch's decode. The
graft is a jitted lambda (``jit__lambda(<fingerprint>)``). So, until the
program names its programs: among the ``jit__unknown`` programs the one run
most often is the decode step (it runs once per wave; a prompt length
recurs only on admission) and the rest are prefills.
"""
from typing import Dict, List

UNNAMED = "jit__unknown("
GRAFT = "jit__lambda("
# kernel name (as a metric names it) -> pattern over trace operation names
KERNELS = {
    "flash_attention": r"^%flash_attention(\.\d+)?$",
    "ssd": r"^%ssd(\.\d+)?$",
}


def classify(module_n: Dict[str, int]) -> Dict[str, List[str]]:
    """Program names by kind: ``prefill``, ``graft``, ``decode``."""
    unnamed = {n: c for n, c in module_n.items() if n.startswith(UNNAMED)}
    decode = [max(sorted(unnamed), key=unnamed.get)] if unnamed else []
    return {"prefill": sorted(n for n in unnamed if n not in decode),
            "graft": sorted(n for n in module_n if n.startswith(GRAFT)),
            "decode": decode}


def seconds(red, kind: str) -> float:
    """Device seconds of one kind of program in a reduction."""
    return sum(red.module_s[n] for n in classify(red.module_n)[kind])
