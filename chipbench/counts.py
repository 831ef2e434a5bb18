"""Operations and bytes the kernels need, computed from shapes, and the
chip's peaks. Nothing here reads the program: a configuration's sizes
(the ``model`` group of its file) and the call's lengths are the input.
A whole model step's FLOPs are counted by the configuration's own plain
reference (``reference/<name>.py``: ``prefill_flops``, ``decode_flops``).

FLOPs count a multiply-add as two. A causal attention or SSD needs only the
lower triangle, so that is what is counted; work a kernel spends on masked
entries is its own loss, not the algorithm's need.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"({path.name}) has {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, pk: Dict[str, Any]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])


# --- kernels -----------------------------------------------------------------
def flash_flops(m: Dict[str, Any], length: int) -> float:
    """Causal self-attention of one sequence, all heads: QK^T and PV over
    the L(L+1)/2 query-key pairs."""
    pairs = length * (length + 1) / 2
    return 4.0 * m["n_heads"] * m["head_dim"] * pairs


def flash_bytes(m: Dict[str, Any], length: int, act_bytes: int = 2) -> float:
    """Q, K, V read once and O written once."""
    q = length * m["n_heads"] * m["head_dim"]
    kv = length * m["n_kv_heads"] * m["head_dim"]
    return act_bytes * (2 * q + 2 * kv)


def _ssd_heads(m):
    di = m["ssm_expand"] * m["d_model"]
    return di // m["ssm_headdim"], m["ssm_headdim"], m["ssm_state"]


def ssd_flops(m: Dict[str, Any], length: int) -> float:
    """Chunked SSD of one sequence: per chunk of q positions the causal
    C B^T and its product with x (q(q+1)/2 pairs each), the entering state's
    contribution C h and the state update x^T B (q P N each), per head."""
    h, p, n = _ssd_heads(m)
    chunk = m["ssm_chunk"]
    total = 0.0
    for start in range(0, length, chunk):
        q = min(chunk, length - start)
        pairs = q * (q + 1) / 2
        total += 2 * pairs * (n + p) + 4 * q * p * n
    return h * total


def ssd_bytes(m: Dict[str, Any], length: int, act_bytes: int = 2) -> float:
    """x, B, C in the activation type and dt in f32 read once; y and the
    final state written once in f32."""
    h, p, n = _ssd_heads(m)
    g = m["ssm_ngroups"]
    reads = length * (h * p * act_bytes + h * 4 + 2 * g * n * act_bytes)
    writes = length * h * p * 4 + h * p * n * 4
    return reads + writes
