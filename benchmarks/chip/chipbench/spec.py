"""Load a cell: its workload file and the configuration file it names."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CONFIG_DIR = BENCH_DIR / "configs"
WORKLOAD_DIR = BENCH_DIR / "workloads"
METRIC_DIR = BENCH_DIR / "metrics"


@dataclass(frozen=True)
class Cell:
    name: str
    workload: dict
    config: dict

    @property
    def serve(self) -> dict:
        return self.workload["serve"]

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]

    @property
    def limits(self) -> dict:
        return self.workload["limits"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, workload_dir: Path = WORKLOAD_DIR,
              config_dir: Path = CONFIG_DIR) -> Cell:
    wpath = workload_dir / f"{name}.json"
    if not wpath.is_file():
        raise FileNotFoundError(f"no workload file {wpath}")
    workload = load_json(wpath)
    config = load_json(config_dir / f"{workload['config']}.json")
    return Cell(name, workload, config)


def model_dims(config: dict) -> dict:
    """The published sizes (Hugging Face `config.json` keys) as the
    benchmark's own names."""
    c = config["config"]
    n_heads = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": n_heads,
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // n_heads,
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "tied": bool(c.get("tie_word_embeddings", False)),
        "qkv_bias": bool(config["qkv_bias"]),
    }
