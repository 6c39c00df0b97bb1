"""A throwaway benchmark at toy size for the CPU tests: its own
BENCHMARK.json, configuration and mix as new files in a temporary
directory, beside copies of the real generators, metrics, references and
peaks (which the harness then finds by name, as it finds the real ones)."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TINY_CONFIG = {
    "name": "tiny", "source": "toy size for CPU tests", "family": "dense",
    "reference": "decoder", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "engine": {"instances": 2, "max_slots": 2, "max_seq": 64,
               "page_size": 8, "recovery": "kevlarflow",
               "replication": "delta"},
    "check": {"sample_tokens": 24, "mean_gap_limit": 0.0005},
}
TINY_MOE = dict(TINY_CONFIG, name="tiny-moe", family="moe",
                num_local_experts=4, num_experts_per_tok=2)
TINY_MIX = {
    "generator": "lengths_arrivals",
    "prompt": {"mean": 16, "sigma": 0.6, "min": 8, "max": 24},
    "output": {"mean": 24, "sigma": 0.4, "min": 12, "max": 40},
    "arrivals": {"kind": "poisson", "rate_per_s": 20.0},
}


def make(tmp: Path, config: dict = TINY_CONFIG, mix: dict = TINY_MIX,
         extra_metric: str | None = None) -> Path:
    """A checkout-like directory holding one cell ``tiny_cell``."""
    bench = tmp / "bench"
    for part in ("generators", "metrics", "references"):
        shutil.copytree(ROOT / "bench" / part, bench / part)
    shutil.copy(ROOT / "bench" / "peaks.json", bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps(mix))
    per_layer = [{"name": "step_ms", "unit": "ms", "better": "lower",
                  "source": "program_span", "layer": "data plane",
                  "moves": "tpot_p90_ms"}]
    if extra_metric:
        (bench / "metrics" / f"{extra_metric}.py").write_text(
            "def read(run):\n    return len(run.measured)\n")
        per_layer.append(dict(per_layer[0], name=extra_metric))
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": config["name"], "source": "toy",
                     "file": f"bench/configs/{config['name']}.json",
                     "reduced": [], "why": "toy"}],
        "workloads": [{"name": "tiny_cell", "config": config["name"],
                       "traffic": "tiny_mix", "chips": 1, "why": "toy"}],
        "end_to_end": [
            {"name": "tpot_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer}))
    return tmp

