"""Find a cell's configuration, traffic mix, checks and metric readers by
the names in ``BENCHMARK.json``.

Layout under the benchmark's directory (``ROOT``):

- ``configs/<config>.json``: the configuration as it is run (the sizes, the
  source, ``reduced`` and ``assumed``, the spec overrides, the reduced
  sizes of the CPU rehearsal); ``configs/<config>.ref.py`` beside it is its
  plain float32 reference.
- ``traffic/<traffic>.json``: a traffic mix, read by ``traffic.generate``.
- ``checks/<workload>.json``: the limits of the numbers that decide
  ``correct``, with the readings they were set from.
- ``metrics/<metric>.py``: one reader per per-layer metric.

A new configuration, mix, cell or metric is a new file and a new entry in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
REPO = ROOT.parents[1]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file by path (file names carry dots, so not by import)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    root: Path
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(self.root / "configs" / f"{self.config_name}.ref.py",
                           f"chipbench_ref_{self.config_name}")

    def metric_reader(self, name: str):
        return load_module(self.root / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}")


def _reports(metric: Dict[str, Any], cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_of_cell


def find_cell(name: str, repo: Path = REPO,
              root: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``<repo>/BENCHMARK.json``; ``root`` is the
    benchmark's directory (default: beside this package)."""
    root = Path(root) if root is not None else ROOT
    bench = load_json(Path(repo) / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(Path(repo) / cfg["file"])
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    checks_path = root / "checks" / f"{name}.json"
    checks = load_json(checks_path) if checks_path.exists() else {}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                checks=checks, root=root, end_to_end=e2e,
                per_layer=per_layer)


def load_peaks(device_kind: str, root: Optional[Path] = None
               ) -> Dict[str, float]:
    """The published peaks of one chip, keyed by JAX's ``device_kind``. A
    device that is not in the table is an error, never a default."""
    table = load_json((Path(root) if root else ROOT) / "peaks.json")
    devices = table["devices"]
    if device_kind not in devices:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(devices)}); add its published peaks "
                       f"with their source")
    return dict(devices[device_kind])
