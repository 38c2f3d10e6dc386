"""Finding a cell's files by name, and the device it may run on.

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name that ``BENCHMARK.json`` gives it:

* ``perfbench/configs/<config>.json``  (the entry's ``file``)
* ``perfbench/arch/<model_type>.py``  (the configuration's ``model_type``:
  its program config, weights, reference and work counts)
* ``perfbench/traffic/<traffic>.json``
* ``perfbench/checks/<workload>.json``  (the limits of the correctness check)
* ``perfbench/metrics/<stem>.py``, where ``<stem>`` is a metric's name up to
  its first ``.`` (``decode_step_ms.chat`` is read by ``decode_step_ms.py``)

so a later change adds a cell, a mix or a metric by adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys

#: Root of the checkout: the directory that holds ``BENCHMARK.json``.
ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoDevice(RuntimeError):
    """The machine holds no accelerator the benchmark can measure."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: list
    per_layer: list
    arch: object


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, traffic and limits."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / "perfbench"
    config = _load_json(root / cfg_entry["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        checks=_load_json(bench_dir / "checks" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        arch=arch(config, root))


def arch(cfg: dict, root: pathlib.Path = ROOT):
    """The module ``perfbench/arch/<model_type>.py`` of the checkout at
    ``root`` for a configuration file; a ``model_type`` without one is an
    error, never a default."""
    model_type = str(cfg.get("model_type"))
    path = root / "perfbench" / "arch" / f"{model_type}.py"
    if not (model_type.isidentifier() and path.is_file()):
        raise SystemExit(
            f"configuration {cfg.get('name')!r} has model_type "
            f"{model_type!r} and no module for it: add "
            f"perfbench/arch/{model_type}.py (its interface is in "
            f"perfbench/arch/__init__.py)")
    name = f"perfbench.arch.{model_type}"
    if path.resolve() == (ROOT / "perfbench" / "arch" / path.name).resolve():
        return importlib.import_module(name)
    # A module of another checkout: keyed by its path, so that it never
    # stands in for this checkout's module of the same name.
    key = f"{name}@{path.resolve()}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        spec.loader.exec_module(module)
    return sys.modules[key]


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """The peaks of ``device_kind`` ``kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = _load_json(root / "perfbench" / "peaks.json")
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in perfbench/peaks.json "
                       f"({sorted(table)}); nothing was measured")
    return table[kind]


def trace_names(root: pathlib.Path = ROOT) -> dict:
    """Program and kernel names as the device trace shows them."""
    return _load_json(root / "perfbench" / "trace_names.json")


def metric_reader(metric_name: str):
    """The ``read`` function of ``perfbench/metrics/<stem>.py``."""
    stem = metric_name.split(".", 1)[0]
    return importlib.import_module(f"perfbench.metrics.{stem}").read


def check_device(chips: int, root: pathlib.Path = ROOT) -> tuple[dict, dict]:
    """The device stamp and its peaks; raises ``NoDevice`` unless JAX sees
    at least ``chips`` accelerators of a kind in the peaks table."""
    import jax

    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if stamp["platform"] == "cpu":
        raise NoDevice("no accelerator found (JAX sees only the CPU); "
                       "nothing was measured")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}; nothing was measured")
    return stamp, load_peaks(stamp["kind"], root)


def log(*parts) -> None:
    """One line of progress on standard error."""
    print(*parts, file=sys.stderr, flush=True)
