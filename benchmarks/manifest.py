"""Loads BENCHMARK.json and the data files a cell names.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own, found by the name the manifest
gives it:

    benchmarks/configs/<config>.json         sizes, runtime, HBM accounting
    benchmarks/traffic/<traffic>.json        parameters of the one generator
    benchmarks/cells/<cell>.json             the cell's fixed rate or callers
    benchmarks/layer_metrics/<metric>.json   unit, layer, moves, reader
    benchmarks/readers/<reader>.py           read(ctx) -> float | None
    benchmarks/architectures/<name>.py       the kind of model a configuration is

The harness knows no cell, model or metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# what an architecture file gives (benchmarks/README.md says what each takes
# and returns); a configuration that names none is of the first kind there was
ARCHITECTURE = ("model", "params", "forward_top2", "weight_bytes", "state_bytes_per_token",
                "decode_step", "prefill_chunk")
DEFAULT_ARCHITECTURE = "dense-gqa"


class ManifestError(ValueError):
    """BENCHMARK.json or one of the files it names is not usable."""


def check_name(value: Any, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what} {value!r}: not a name (letters, digits, _ . -; at most 64)")
    return value


def check_unit(value: Any, what: str) -> str:
    if not isinstance(value, str) or not UNIT_RE.match(value):
        raise ManifestError(f"{what}: unit {value!r} breaks the character rules")
    return value


def _load_json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{what}: {path} is not JSON ({e})") from None
    if not isinstance(data, dict):
        raise ManifestError(f"{what}: {path} does not hold an object")
    return data


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None = None  # per-layer metrics only
    moves: str | None = None
    read: Callable[[Any], Any] | None = None  # per-layer metrics only


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    params: dict  # benchmarks/cells/<cell>.json: rate_rps or callers
    arch: Any = None  # the module benchmarks/architectures/<config's architecture>.py
    end_to_end: tuple[Metric, ...] = field(default_factory=tuple)
    per_layer: tuple[Metric, ...] = field(default_factory=tuple)


def load_manifest(root: str = ROOT) -> dict:
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"), "manifest")
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in manifest:
            raise ManifestError(f"BENCHMARK.json lacks {key!r}")
    seen: set[str] = set()
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        check_name(entry.get("name"), "metric")
        check_unit(entry.get("unit"), f"metric {entry['name']}")
        if entry["name"] in seen:
            raise ManifestError(f"metric {entry['name']!r} appears twice")
        seen.add(entry["name"])
        if entry.get("better") not in ("lower", "higher"):
            raise ManifestError(f"metric {entry['name']}: better must be lower or higher")
        if entry.get("source") not in SOURCES:
            raise ManifestError(f"metric {entry['name']}: unknown source {entry.get('source')!r}")
    for entry in manifest["configs"] + manifest["workloads"]:
        check_name(entry.get("name"), "entry")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for entry in manifest["per_layer"]:
        if entry.get("moves") not in e2e:
            raise ManifestError(
                f"per-layer metric {entry['name']} moves {entry.get('moves')!r}, "
                "which is no end-to-end metric"
            )
    return manifest


def _load_module(kind: str, name: str, here: str) -> Any:
    """The module benchmarks/<kind>s/<name>.py, by file (a later PR adds
    one by adding a file; nothing imports it by name)."""
    check_name(name, kind)
    path = os.path.join(here, f"{kind}s", f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"unknown {kind} {name!r}: no {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"benchmarks_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, here: str = HERE) -> Callable[[Any], Any]:
    module = _load_module("reader", name, here)
    if not callable(getattr(module, "read", None)):
        raise ManifestError(f"reader {name!r} has no read(ctx)")
    return module.read


def load_architecture(name: str, here: str = HERE) -> Any:
    """The architecture a configuration names: the program's model
    description, the seeded weights, the plain reference and the
    operation counts of one kind of model, as one module."""
    module = _load_module("architecture", name, here)
    missing = [f for f in ARCHITECTURE if not callable(getattr(module, f, None))]
    if missing:
        raise ManifestError(f"architecture {name!r} lacks {', '.join(missing)}")
    return module


def _in_cell(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def resolve_cell(manifest: dict, cell_name: str, root: str = ROOT) -> Cell:
    """Everything one run of one cell needs, or ManifestError."""
    here = os.path.join(root, "benchmarks")
    rows = [w for w in manifest["workloads"] if w.get("name") == cell_name]
    if not rows:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"unknown workload {cell_name!r} (known: {known})")
    row = rows[0]
    if row.get("chips") not in (1, 4):
        raise ManifestError(f"cell {cell_name}: chips must be 1 or 4")
    configs = {c["name"]: c for c in manifest["configs"]}
    if row.get("config") not in configs:
        raise ManifestError(f"cell {cell_name}: unknown config {row.get('config')!r}")
    config = _load_json(os.path.join(root, configs[row["config"]]["file"]), "config")
    traffic_name = check_name(row.get("traffic"), "traffic")
    traffic = _load_json(os.path.join(here, "traffic", f"{traffic_name}.json"), "traffic")
    params = _load_json(os.path.join(here, "cells", f"{cell_name}.json"), "cell")
    arch = load_architecture(config.get("architecture", DEFAULT_ARCHITECTURE), here)

    e2e = tuple(
        Metric(m["name"], m["unit"], m["better"], m["source"])
        for m in manifest["end_to_end"] if _in_cell(m, cell_name)
    )
    reported = {m.name for m in e2e}
    layer: list[Metric] = []
    for m in manifest["per_layer"]:
        if not _in_cell(m, cell_name):
            continue
        if m["moves"] not in reported:
            raise ManifestError(
                f"per-layer metric {m['name']} moves {m['moves']}, which cell "
                f"{cell_name} does not report"
            )
        spec = _load_json(
            os.path.join(here, "layer_metrics", f"{m['name']}.json"),
            f"unknown metric {m['name']!r}",
        )
        for key in ("unit", "layer", "moves", "source"):
            if spec.get(key) != m.get(key):
                raise ManifestError(
                    f"metric {m['name']}: {key} differs between BENCHMARK.json "
                    f"({m.get(key)!r}) and its file ({spec.get(key)!r})"
                )
        layer.append(Metric(
            m["name"], m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            load_reader(spec.get("reader", m["name"]), here),
        ))
    return Cell(cell_name, row["chips"], row["config"], config, traffic_name,
                traffic, params, arch, e2e, tuple(layer))


def unregistered(cell: Cell, root: str = ROOT) -> list[Metric]:
    """Per-layer metrics that have a file and no entry for this cell in
    BENCHMARK.json (the end-to-end metric they move is not reported here
    yet): a traced run records them in its log, never in its result."""
    here = os.path.join(root, "benchmarks")
    named = {m.name for m in cell.per_layer}
    out = []
    for file in sorted(os.listdir(os.path.join(here, "layer_metrics"))):
        name, ext = os.path.splitext(file)
        if ext == ".json" and name not in named:
            spec = _load_json(os.path.join(here, "layer_metrics", file), f"metric {name}")
            out.append(Metric(name, spec["unit"], spec["better"], spec["source"], spec["layer"],
                              spec["moves"], load_reader(spec.get("reader", name), here)))
    return out


def load_peaks(device_kind: str, here: str = HERE) -> dict:
    """Published peaks of the chip, by exact device_kind; unknown = error."""
    table = _load_json(os.path.join(here, "peaks.json"), "peaks")
    if device_kind not in table.get("devices", {}):
        raise ManifestError(
            f"unknown device_kind {device_kind!r}: add it to benchmarks/peaks.json "
            "with its source"
        )
    return table["devices"][device_kind]
