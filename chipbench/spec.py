"""What a cell is made of, read from ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``configs/<name>.json``: the model's sizes,
the engine's sizes, the limit that decides ``correct`` and the reduced
sizes for a CPU rehearsal) and a traffic mix (``traffic/<name>.json``:
parameters for ``generator.py``).  End-to-end and per-layer metrics are
small readers found by name under ``end_to_end/`` and ``layer_metrics/``.
Adding any of these is adding a file and an entry; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<name>.json
    mix: dict             # traffic/<name>.json
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=load_json(ROOT / conf["file"]),
        mix=load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def rehearsal_cell(config: str, mix: str) -> Cell:
    """A one-chip cell of a configuration and a mix by file name, with
    no metrics: for the tests and scripts that drive the harness at
    rehearsal sizes, whether or not ``BENCHMARK.json`` has that cell."""
    return Cell(name=f"{config}.{mix}", chips=1,
                config=load_json(HERE / "configs" / f"{config}.json"),
                mix=load_json(HERE / "traffic" / f"{mix}.json"),
                end_to_end=[], per_layer=[])


def reader(kind: str, name: str):
    """The module ``<kind>/<name>.py``: a metric's reader."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sizes(config: dict, rehearse: bool) -> tuple[dict, dict]:
    """(model sizes, engine sizes) as run; the rehearsal's tiny ones on
    the CPU."""
    model, engine = dict(config["model"]), dict(config["engine"])
    if rehearse:
        model.update(config["rehearsal"]["model"])
        engine.update(config["rehearsal"]["engine"])
    return model, engine


def limit(config: dict, rehearse: bool) -> float:
    """The limit on the widest logit gap: the configuration's, or at the
    rehearsal's tiny sizes the rehearsal's own (set from CPU readings,
    since a model of 64 widths rounds differently from the real one)."""
    src = config["rehearsal"] if rehearse else config
    return src["correct"]["max_logit_gap"]


def model_config(config: dict, rehearse: bool = False):
    """The program's ``ModelConfig`` on the live paged + Pallas path."""
    from repro.configs.base import ModelConfig
    model, _ = sizes(config, rehearse)
    if "mrope_sections" in model:
        model["mrope_sections"] = tuple(model["mrope_sections"])
    return ModelConfig(name=config["name"], family="dense", use_pallas=True,
                       **model)


def scheduler_config(config: dict, rehearse: bool = False):
    """Unified role, paged pool, stall-free mixed steps."""
    from repro.serving.scheduler import SchedulerConfig
    _, engine = sizes(config, rehearse)
    return SchedulerConfig(
        max_slots=engine["max_slots"], max_context=engine["max_context"],
        page_size=engine["page_size"], num_pages=engine["num_pages"],
        max_batch_tokens=engine["max_batch_tokens"], mixed=True)
