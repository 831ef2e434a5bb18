"""Finds the benchmark's parts by name. Nothing is registered in code:

  workloads/<cell>.json     a cell: its configuration, traffic, sizes, rate, check
  configs/<config>.json     a configuration: the sizes it runs and its reference
  reference/<name>.py       a plain reference: ``init_spec`` (weights), ``hidden``
                            (the check), ``prefill_flops`` and ``decode_flops``
                            (one model step's FLOPs, read by the mfu metrics)
  traffic/<traffic>.json    a traffic mix, read by ``gen.Traffic``
  metrics/<metric>.py       a per-layer metric: ``read(ctx) -> float | None``; a
                            kernel's roofline also holds the ``PATTERN`` that
                            finds the kernel's operations in a trace

A later change adds a cell, configuration, mix or metric by adding files. A
configuration of a family no reference covers brings its reference, all four
functions in its one new file.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: Path = CHECKOUT) -> Dict[str, Any]:
    return _json(checkout / "BENCHMARK.json")


def cell(name: str, root: Path = HERE) -> Dict[str, Any]:
    """The cell file with its configuration and traffic mix attached."""
    c = _json(root / "workloads" / f"{name}.json")
    c["name"] = name
    c["config_spec"] = _json(root / "configs" / f"{c['config']}.json")
    c["traffic_spec"] = _json(root / "traffic" / f"{c['traffic']}.json")
    return c


REFERENCE_API = ("init_spec", "hidden", "prefill_flops", "decode_flops")


def reference(name: str):
    """The plain reference module ``reference/<name>.py``; one that lacks
    any of ``REFERENCE_API`` raises."""
    mod = importlib.import_module(f"reference.{name}")
    missing = [f for f in REFERENCE_API if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"reference {name!r} lacks {missing}; a reference "
                             f"gives {list(REFERENCE_API)}")
    return mod


def metric_module(name: str, root: Path = HERE):
    """``metrics/<name>.py``, loaded; the name may hold dots."""
    path = root / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if mod_spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = HERE):
    """``read`` of ``metrics/<name>.py``."""
    return metric_module(name, root).read


def per_layer_for(cell_name: str, bench: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The per-layer metrics that list this cell, or list no cells at all."""
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def end_to_end_for(cell_name: str, bench: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
