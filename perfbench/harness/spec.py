"""BENCHMARK.json and the files it names, found by name."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_module(path: Path, name: str):
    """Import a file by path; ``name`` need not be a Python identifier."""
    if not Path(path).is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, bench: dict, workload: str, bench_dir: Path = BENCH_DIR,
                 root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload named {workload!r} in BENCHMARK.json")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(bench_dir / "traffic" / f"{self.traffic_name}.json")
        self.limits = load_json(bench_dir / "cells" / f"{workload}.json")
        self.bench_dir = bench_dir
        self.end_to_end = [m for m in bench["end_to_end"] if _applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]

    def reference(self):
        ref = self.config["reference"]
        return load_module(self.bench_dir / "configs" / f"{ref}.py", ref)

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end

    def reader(self, metric: str):
        return load_module(reader_path(self.bench_dir, metric), metric)

    def driver(self):
        """``harness/<kind>.py`` for the traffic's ``kind``: its ``Run`` and
        ``check``."""
        kind = str(self.traffic["kind"])
        if not kind.isidentifier() or not (BENCH_DIR / "harness" / f"{kind}.py").is_file():
            raise SpecError(f"no run driver for traffic kind {kind!r}")
        return importlib.import_module(f"harness.{kind}")


def reader_path(bench_dir: Path, metric: str) -> Path:
    """``metrics/<metric>.py``, or the reader of the name with its last
    ``.<suffix>`` dropped: ``device_idle_share.train`` is read by
    ``device_idle_share.py`` unless a file of its own is there."""
    name = metric
    while True:
        path = bench_dir / "metrics" / f"{name}.py"
        if path.is_file() or "." not in name:
            return path
        name = name.rsplit(".", 1)[0]


def load_cell(workload: str, bench_path: Path | None = None) -> Cell:
    """The cell named ``workload`` in ``bench_path`` (BENCHMARK.json by
    default); a file inside ``perfbench/`` resolves against the checkout."""
    bench_path = Path(bench_path or ROOT / "BENCHMARK.json").resolve()
    root = bench_path.parent
    if root.name == "perfbench":
        root = root.parent
    return Cell(load_json(bench_path), workload, root / "perfbench", root)
