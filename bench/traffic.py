"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; its ``kind`` names the arrival process, a module
``bench/traffic/<kind>.py`` that drives the window (see ``backlog.py``
and ``poisson.py``).  A new mix of an existing kind is a new JSON file;
a new kind is a new module beside them.  A kind module gives:

* ``buckets(mix, ladder)``: the bucket shapes its traffic uses, which
  the harness warms before the window;
* ``window(loop, mix, seed, seconds)``: drives ``run.Loop`` for the
  window and returns the harness's handle of every request it counts.

Each request's image is drawn from a pool of ``pool`` seeded int8
images (3 x H x W, uniform over int8), in an order the seed draws.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent / "traffic"


def load(path: Path) -> dict:
    """The mix's parameters, with its arrival process under ``"process"``."""
    mix = json.loads(Path(path).read_text())
    kind = HERE / f"{mix.get('kind')}.py"
    if not kind.exists():
        raise ValueError(f"{path}: no arrival process {kind.name} for kind "
                         f"{mix.get('kind')!r}")
    spec = importlib.util.spec_from_file_location(f"traffic_{kind.stem}", kind)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return dict(mix, process=mod)


def images(mix: dict, seed: int, shape) -> np.ndarray:
    """(pool, *shape) int8 images drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    return rng.integers(-128, 128, (int(mix["pool"]), *shape), dtype=np.int8)


def image_order(mix: dict, seed: int, n: int) -> np.ndarray:
    """Which pool image each of ``n`` requests carries."""
    return np.random.default_rng([seed, 3]).integers(0, int(mix["pool"]), n)
