"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; this
module turns those names into files under ``bench/``:

  configuration   bench/configs/<config>.json
  traffic mix     bench/traffic/<traffic>.json, whose "generator" names
                  bench/generators/<generator>.py
  per-layer metric bench/metrics/<metric>.py, or for ``<base>.<variant>``
                  bench/metrics/<base>.py when no file of the full name
                  exists
  reference       bench/references/<reference>.py, named by the config
  peaks           bench/peaks.json, keyed by JAX's ``device_kind``

A later cell, configuration, mix or metric is a new file and a new entry:
nothing here needs an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def add_program_to_path(root: Path = ROOT):
    """The program under test lives in ``<root>/src``."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """Import one file as a module of its own (names may hold dots), once
    per path."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_part_" + "_".join(path.relative_to(path.parents[1]).parts)
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``bench_dir``."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        self.spec = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        return load_json(self.dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def generator(self, traffic: dict):
        return load_module(self.dir / "generators"
                           / f"{traffic['generator']}.py")

    def reference(self, config: dict):
        return load_module(self.dir / "references"
                           / f"{config['reference']}.py")

    def metric_reader(self, name: str):
        path = self.dir / "metrics" / f"{name}.py"
        if not path.is_file() and "." in name:
            path = self.dir / "metrics" / f"{name.split('.')[0]}.py"
        return load_module(path)

    def metrics_for(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports: a
        metric without a ``workloads`` list belongs to every cell."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.dir / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in bench/peaks.json "
                f"(known: {sorted(table['devices'])}); add its published "
                "peaks rather than guess them")
        return table["devices"][device_kind]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file (Hugging Face
    key names, as the source publishes them)."""
    from repro.configs.base import ModelConfig
    experts = conf.get("num_local_experts", 0)
    return ModelConfig(
        name=conf["name"], arch_type=conf["family"],
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], n_experts=experts,
        top_k=conf.get("num_experts_per_tok", 0),
        sliding_window=conf.get("sliding_window") or 0,
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"],
        page_size=conf["engine"]["page_size"], source=conf["source"])


def engine_config(conf: dict):
    """The program's ``EngineConfig`` for a configuration file's engine."""
    from repro.serving.engine import EngineConfig
    e = conf["engine"]
    return EngineConfig(max_slots=e["max_slots"], max_seq=e["max_seq"],
                        recovery=e["recovery"], replication=e["replication"],
                        replicate=e["recovery"] == "kevlarflow")
