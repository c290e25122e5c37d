"""Program builder of the test-only DDPM family: the program's DiT
engine on a cosine noise schedule, served by the mix's sampler (ancestral
DDPM, unguided); each request's condition is a class label drawn from a
Zipf law, so the first classes come far more often than the rest."""
from typing import Dict, List

import numpy as np

from chipbench import spec

_dit = spec.load_module(spec.HERE / "configs" / "dit_program.py")


def build(cfg: Dict, traffic: Dict, params, devices):
    from repro.diffusion import schedule as sch
    ns = cfg["noise_schedule"]
    if ns["kind"] != "cosine":
        raise ValueError(f"noise schedule {ns['kind']!r}; this builder "
                         f"makes the cosine one")
    return _dit.engine(cfg, traffic, params,
                       sch.cosine_schedule(ns["steps"], ns["s"]), devices)


def draw_conds(cfg: Dict, rng, n: int) -> List[int]:
    w = 1.0 / np.arange(1, cfg["num_classes"] + 1)
    return [int(c) for c in rng.choice(cfg["num_classes"], n, p=w / w.sum())]
