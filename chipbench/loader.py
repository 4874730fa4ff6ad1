"""Import a file of the benchmark by its path.

Configurations, configuration kinds, traffic mixes, arrival processes,
drivers and metric readers are files of their own, found by the name
``BENCHMARK.json``, a configuration or a mix gives; names may hold dots, so
they are imported by path."""

from __future__ import annotations

import importlib.util
import pathlib
import sys

KINDS = pathlib.Path(__file__).resolve().parent / "kinds"
DEFAULT_KIND = "edge_chain"


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


def load_kind(config: dict, directory=KINDS):
    """The module of a configuration's kind: ``<directory>/<kind>.py`` for
    the configuration's ``"kind"`` key, ``edge_chain`` when it has none.

    A kind module builds the configuration's system, makes and warms its
    inputs, counts each request's work and compares the window's answers
    with the reference the configuration names; ``kinds/edge_chain.py``
    documents the functions every kind provides.  A new kind is a new file
    there, and edits nothing that is there."""
    return load_module(pathlib.Path(directory)
                       / f"{config.get('kind', DEFAULT_KIND)}.py")
