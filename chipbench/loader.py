"""Import a file of the benchmark by its path.

Configurations, traffic mixes, arrival processes, drivers and metric
readers are files of their own, found by the name ``BENCHMARK.json`` or a
mix gives; names may hold dots, so they are imported by path."""

from __future__ import annotations

import importlib.util
import pathlib
import sys


def load_module(path):
    path = pathlib.Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
