"""Traffic: mixes and the kinds that drive them, both found by name.

A mix file (``<bench>/traffic/<name>.json``) is parameters only.  Its
``mode`` names the traffic kind that reads it: the module
``<bench>/traffic/<mode>.py``, which builds the run's plan from the mix,
the deployment's transfers and ``--seed``, drives the program's entry
through the window and checks what it produced.  A kind module has

* ``run(cfg, mix, data, seed, seconds, recorder, clock_start, hook)``,
  which returns the run's record (``mode``, ``setup_s``, ``window_s``,
  ``attempted``, ``failed``, ``outputs`` and what its metrics read);
* ``check(cfg, mix, data, rec, seed, produce=None)``, which returns the
  compared numbers with their limits and lines of notes;
* ``control(cfg, mix, data, seed, produce, seconds, size)``, which
  scores the control (:mod:`chipbench.control`) as ``check`` scores a
  ``seconds`` run that did ``size`` units of work, on the seeds such a
  run would draw.

A new mix of a kind is one data file; a new kind is one module and a
mix that names it.  The same seed gives the same plan; different seeds
give the same sizes and rates at other places in the data.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType

import numpy as np

__all__ = ["load_kind", "load_mix", "rng_for"]


def rng_for(seed: int, *tag: int) -> np.random.Generator:
    """A generator for ``seed`` (any integer) and a purpose ``tag``."""
    return np.random.default_rng([int(seed) % 2**64, *tag])


def load_mix(bench_dir: str, name: str) -> dict:
    path = os.path.join(bench_dir, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    mode = mix.get("mode")
    if not isinstance(mode, str) or not os.path.isfile(_kind_path(bench_dir, mode)):
        raise ValueError(f"{path}: mode {mode!r} names no traffic kind in {bench_dir}/traffic")
    return mix


def _kind_path(bench_dir: str, mode: str) -> str:
    return os.path.join(bench_dir, "traffic", f"{mode}.py")


def load_kind(bench_dir: str, mode: str) -> ModuleType:
    """The traffic kind ``mode``: ``<bench_dir>/traffic/<mode>.py``,
    loaded once per path."""
    path = os.path.abspath(_kind_path(bench_dir, mode))
    name = "chipbench_traffic_" + mode.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod
