"""What the benchmark runs, found by name under the checkout.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists its metrics. Everything that belongs to one configuration, mix,
limit or metric sits in a file of its own, found by that name:

* ``bench/configs/<config>.json`` — the model as it is served;
* ``bench/traffic/<traffic>.json`` — the parameters of one mix;
* ``bench/limits/<cell>.json`` — the limit that decides ``correct``;
* ``bench/metrics/<metric>.py`` — the reader of one metric. A metric
  named ``base.variant`` may share ``bench/metrics/<base>.py``.

So a new cell, mix or metric is new files and new entries, never an edit.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import pathlib

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def norm_eps(cfg: dict) -> float:
    return cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))


def seed32(seed: int, stream: str) -> int:
    """A 31-bit seed for one named stream of ``seed`` (any whole number)."""
    digest = hashlib.sha256(f"{seed}/{stream}".encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed32(seed, stream))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None   # None: every cell
    moves: str | None = None            # per-layer metrics only

    def applies(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric(entry: dict) -> Metric:
    w = entry.get("workloads")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], tuple(w) if w is not None else None,
                  entry.get("moves"))


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; known: {sorted(entries)}")
    w = entries[name]
    e2e = tuple(m for m in map(_metric, bench["end_to_end"])
                if m.applies(name))
    per_layer = tuple(m for m in map(_metric, bench["per_layer"])
                      if m.applies(name))
    return Cell(
        name=name,
        config=load_json(root / "bench" / "configs" / f"{w['config']}.json"),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(run, variant)`` function of ``metric``'s reader file."""
    base, _, variant = metric.partition(".")
    for stem in (metric, base):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return lambda run: mod.read(run, variant)
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{root / 'bench' / 'metrics'}")
