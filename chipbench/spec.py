"""Finds a cell's files by the names in ``BENCHMARK.json``.

A configuration file names the three modules that know its model, each
found beside the file or else under ``configs/``:

``reference``  the plain reference: ``make_params(cfg, seed, device)``,
               the seeded weights that the program and the reference
               both run, and ``sample(params, cfg, keys, conds, modes,
               sampler, precision)``, each request's x0
``program``    the program under test: ``build(cfg, traffic, params,
               devices)``, a warmable engine over the cell's ``chips``
               devices, and ``draw_conds(cfg, rng, n)``, the conditions
               of n requests from the seed's generator
``work``       needed work, importing nothing of the program:
               ``step_modes(cfg, T, budget)``, the patch mode of each
               denoising step, and ``request_flops``,
               ``request_attention`` and ``request_block_flops`` of given
               steps of one request
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
CONFIG_MODULES = ("reference", "program", "work")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict           # the configuration file, as run
    traffic: Dict          # the traffic mix's parameters
    limits: Dict           # the correctness limits of this cell
    end_to_end: Dict[str, str]  # end-to-end metrics it reports: unit
    per_layer: Dict[str, str]   # per-layer metrics it reports: unit
    reference: ModuleType  # the configuration's plain reference
    program: ModuleType    # builds the program under test
    work: ModuleType       # the configuration's needed work

    def draw_conds(self, rng, n: int) -> List:
        """The conditions of ``n`` requests, drawn from ``rng``."""
        return self.program.draw_conds(self.config, rng, n)


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(config_path: Path, name: str) -> ModuleType:
    """The module ``name`` that a configuration file names: beside the
    file, or else under ``configs/``."""
    for where in (config_path.parent, HERE / "configs"):
        path = where / f"{name}.py"
        if path.is_file():
            return load_module(path)
    raise FileNotFoundError(f"{config_path.name} names module {name!r}, "
                            f"found neither beside it nor under configs/")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return make_cell(
        name, w["chips"],
        config_path=root / cfg_entry["file"],
        traffic_path=HERE / "traffic" / f"{w['traffic']}.json",
        limits_path=HERE / "limits" / f"{name}.json",
        end_to_end={m["name"]: m["unit"] for m in bench["end_to_end"]
                    if _applies(m, name)},
        per_layer={m["name"]: m["unit"] for m in bench["per_layer"]
                   if _applies(m, name)})


def make_cell(name: str, chips: int, *, config_path: Path,
              traffic_path: Path, limits_path: Path,
              end_to_end: Dict[str, str], per_layer: Dict[str, str]
              ) -> Cell:
    config = json.loads(config_path.read_text())
    modules = {k: config_module(config_path, config[k])
               for k in CONFIG_MODULES}
    return Cell(name=name, chips=chips, config=config,
                traffic=json.loads(traffic_path.read_text()),
                limits=json.loads(limits_path.read_text()),
                end_to_end=end_to_end, per_layer=per_layer, **modules)


def metric_reader(name: str) -> Callable:
    """``metrics/<name>.py``'s ``read(run)``: the metric's value, or None
    where the run holds nothing for it to read."""
    return load_module(HERE / "metrics" / f"{name}.py").read
