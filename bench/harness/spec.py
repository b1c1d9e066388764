"""Find a cell, its configuration, its traffic mix and its metrics by
name.  Everything is data: ``BENCHMARK.json`` at the checkout's root,
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``, the
arrival process a mix names in ``bench/arrivals/<arrivals>.py`` and one
reader per per-layer metric in ``bench/metrics/<metric>.py``."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    pass


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    return json.loads(path.read_text())


def load_cell(root: Path, workload: str) -> dict:
    """The cell named ``workload`` with its config, traffic and the
    metric entries that apply to it."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_entry = confs[cell["config"]]
    config = load_json(root / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if "arrivals" not in traffic:
        raise SpecError(f"traffic {cell['traffic']!r} names no arrivals")
    arrivals(traffic["arrivals"])

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` of per-layer metric ``name``."""
    mod = load_module(BENCH / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read


def arrivals(name: str):
    """The arrival process ``name``: a module with ``LOOP`` "open" (and
    ``schedule``) or "closed"."""
    mod = load_module(BENCH / "arrivals" / f"{name}.py",
                      "bench_arrivals_" + name)
    if getattr(mod, "LOOP", None) not in ("open", "closed") or (
            mod.LOOP == "open" and not hasattr(mod, "schedule")):
        raise SpecError(f"arrivals {name!r}: LOOP must be 'open' (with "
                        f"schedule) or 'closed'")
    return mod


def system(name: str):
    return load_module(BENCH / "harness" / "systems" / f"{name}.py",
                       "bench_system_" + name)


def reference(name: str):
    return load_module(BENCH / "references" / f"{name}.py",
                       "bench_reference_" + name)
