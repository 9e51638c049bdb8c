"""Load a cell: its workload file, the configuration file it names and the
model block that configuration names.

A block is `chipbench/blocks/<block>.py` (or a module of that name in the
`block_dir` given to `load_cell`), and defines `BLOCK_FUNCTIONS`:

  model_dims(config) -> dict   the configuration file's sizes under the
                               benchmark's names; the harness itself reads
                               n_layers, n_kv_heads, head_dim (the KV
                               cache's shape) and vocab
  program(config)              the program's model API at those sizes;
                               raises ValueError where the program's model
                               is not this block
  layout(d)                    leaf path -> (shape, kind, fan_in) of the
                               weight tree the program reads
                               (`chipbench/weights.py` draws each kind)
  final_hidden(params, tokens, plen, *, d, keep, precision)
                               the float32 reference's final hidden rows
                               of one sequence (`d` is model_dims as a
                               sorted tuple, `keep` None for raw K/V;
                               jitted with those three static)
  logits_rows(params, h, *, d, precision)
                               its logits of those rows
  matmul_params(d)             weights each token multiplies
  attn_flops(d, context)       one query over `context` positions

A new configuration is a new `configs/<name>.json`, a new block where no
block serves it yet, and a new `workloads/<cell>.json`.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
CONFIG_DIR = BENCH_DIR / "configs"
WORKLOAD_DIR = BENCH_DIR / "workloads"
METRIC_DIR = BENCH_DIR / "metrics"
BLOCK_DIR = BENCH_DIR / "chipbench" / "blocks"

BLOCK_FUNCTIONS = ("model_dims", "program", "layout", "final_hidden",
                   "logits_rows", "matmul_params", "attn_flops")
MESHES = {"2x2": 4, "4x1": 4}


@dataclass(frozen=True)
class Cell:
    name: str
    workload: dict
    config: dict
    block: ModuleType

    @property
    def serve(self) -> dict:
        return self.workload["serve"]

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]

    @property
    def limits(self) -> dict:
        return self.workload["limits"]

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    @property
    def dims(self) -> dict:
        return self.block.model_dims(self.config)

    @property
    def keep(self) -> int | None:
        """The codec's keep, or None where the cell keeps raw K/V."""
        return self.serve["keep"] if self.serve.get("kv_compress", True) \
            else None


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.cache
def _load_outside(path: Path) -> ModuleType:
    """A block that lives outside `chipbench/blocks/`, loaded from its file
    once; what it imports of `chipbench` is the package's own modules."""
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_block_{path.stem}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_block(name, block_dir: Path = BLOCK_DIR) -> ModuleType:
    """The block module a configuration names: `chipbench.blocks.<name>`,
    imported as a module of the package, or `<block_dir>/<name>.py`."""
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"block {name!r} is not a name")
    path = block_dir / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown block {name!r}: no module {path}")
    if path.resolve().parent == BLOCK_DIR.resolve():
        mod = importlib.import_module(f"chipbench.blocks.{name}")
    else:
        mod = _load_outside(path.resolve())
    missing = [f for f in BLOCK_FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"block {name!r} ({path}) does not define {missing}")
    return mod


def _check_serve(name: str, workload: dict) -> None:
    s, chips = workload["serve"], workload["chips"]
    if s.get("kv_compress", True):
        for key in ("pool_pages", "keep", "codec"):
            if key not in s:
                raise ValueError(f"{name}: a compressed cell gives serve.{key}")
        if s["codec"] != "dct":
            raise ValueError(f"{name}: codec {s['codec']!r}; the reference "
                             "writes out the dct family only")
    mesh = s.get("mesh")
    if chips > 1 and MESHES.get(mesh) != chips:
        raise ValueError(f"{name}: a cell on {chips} chips gives serve.mesh, "
                         f"one of {sorted(MESHES)} (has {mesh!r})")
    if chips == 1 and mesh is not None:
        raise ValueError(f"{name}: a one-chip cell has no serve.mesh")


def load_cell(name: str, workload_dir: Path = WORKLOAD_DIR,
              config_dir: Path = CONFIG_DIR,
              block_dir: Path = BLOCK_DIR) -> Cell:
    wpath = workload_dir / f"{name}.json"
    if not wpath.is_file():
        raise FileNotFoundError(f"no workload file {wpath}")
    workload = load_json(wpath)
    _check_serve(name, workload)
    config = load_json(config_dir / f"{workload['config']}.json")
    if "block" not in config:
        raise ValueError(f"configuration {workload['config']!r} names no "
                         "block")
    return Cell(name, workload, config, load_block(config["block"], block_dir))
