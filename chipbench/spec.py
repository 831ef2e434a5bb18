"""Finds the benchmark's parts by name. Nothing is registered in code:

  workloads/<cell>.json     a cell: its configuration, traffic, sizes, rate, check;
                            a gang cell adds ``gang`` and ``events`` (below)
  configs/<config>.json     a configuration: the sizes it runs and its reference
  reference/<name>.py       a plain reference: ``init_spec`` (weights); ``embed``,
                            ``layers`` and ``final`` (the check, which runs
                            ``layers`` over blocks of the stacked layers);
                            ``prefill_flops`` and ``decode_flops`` (one model
                            step's FLOPs, read by the mfu metrics)
  traffic/<traffic>.json    a traffic mix, read by ``gen.Traffic``
  metrics/<metric>.py       a per-layer metric: ``read(ctx) -> float | None``; a
                            kernel's roofline also holds the ``PATTERN`` that
                            finds the kernel's operations in a trace

A later change adds a cell, configuration, mix or metric by adding files. A
configuration of a family no reference covers brings its reference, every
function of ``REFERENCE_API`` in its one new file.

A gang cell serves the model tensor-parallel over several chips and changes
its membership on schedule:

  "gang":   {"members": N, "kv_mode": "migrate" | "migrate_int8" | "replay"}
  "events": [{"at_s": t, "members": n, "grace_s": g}, ...]

``at_s`` counts from the window's opening; each event resizes the gang to
``n`` members and must be over within ``grace_s`` seconds (a number the check
compares). ``cell`` refuses a gang larger than the cell's chips, a kv_mode
the program does not have, events out of time order, a size under 1 or
above the chips, and an event later than ``EVENT_MARGIN_S`` before the
traced span (the window's last ``trace_s`` seconds). ``replay`` re-prefills
each carried request at its prompt plus the tokens it had, lengths that
set-up does not warm: its programs compile inside the window.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: Path = CHECKOUT) -> Dict[str, Any]:
    return _json(checkout / "BENCHMARK.json")


class CellError(ValueError):
    """A cell file that breaks the rules; raised before any set-up, or in the
    window where an event's first engine call reaches the traced span."""


# An event's resize and the first engine call after it run programs of their
# own; they have to be over before the traced span opens
EVENT_MARGIN_S = 1.0


def cell(name: str, root: Path = HERE, chips: Optional[int] = None,
         seconds: Optional[float] = None, trace_s: float = 0.0) -> Dict[str, Any]:
    """The cell file with its configuration and traffic mix attached. Given
    the cell's ``chips`` and the run's window ``seconds``, its gang and
    events are checked against them."""
    c = _json(root / "workloads" / f"{name}.json")
    c["name"] = name
    c["config_spec"] = _json(root / "configs" / f"{c['config']}.json")
    c["traffic_spec"] = _json(root / "traffic" / f"{c['traffic']}.json")
    if chips is not None:
        c["chips"] = chips
        _check_gang(c, chips, seconds, trace_s)
    return c


def _check_gang(c: Dict[str, Any], chips: int, seconds: Optional[float], trace_s: float):
    gang, events = c.get("gang"), c.get("events", [])
    if gang is None:
        if events:
            raise CellError(f"cell {c['name']!r}: events need a gang")
        return
    if not 1 <= gang["members"] <= chips:
        raise CellError(f"cell {c['name']!r}: a gang of {gang['members']} members "
                        f"on {chips} chips")
    from repro.distributed.elastic_serving.migration import KV_MODES
    if gang["kv_mode"] not in KV_MODES:
        raise CellError(f"cell {c['name']!r}: kv_mode {gang['kv_mode']!r}; "
                        f"allowed: {KV_MODES}")
    latest = None if seconds is None else seconds - trace_s - EVENT_MARGIN_S
    at, size = 0.0, gang["members"]
    for e in events:
        if e["at_s"] < at:
            raise CellError(f"cell {c['name']!r}: events out of time order at {e['at_s']} s")
        if not 1 <= e["members"] <= chips or e["members"] == size:
            raise CellError(f"cell {c['name']!r}: an event resizes {size} members "
                            f"to {e['members']} on {chips} chips")
        if latest is not None and e["at_s"] > latest:
            raise CellError(f"cell {c['name']!r}: the event at {e['at_s']} s is within "
                            f"{EVENT_MARGIN_S} s of the traced span, the last {trace_s} s "
                            f"of a {seconds} s window")
        if not e["grace_s"] > 0:
            raise CellError(f"cell {c['name']!r}: grace {e['grace_s']} s")
        at, size = e["at_s"], e["members"]


REFERENCE_API = ("init_spec", "embed", "layers", "final", "prefill_flops", "decode_flops")


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
