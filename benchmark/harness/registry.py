"""Finds what belongs to a cell by name: everything is a file of its own.

``BENCHMARK.json`` (one directory above ``benchmark/``) lists the cells,
configurations and metrics.  A cell names a configuration and a traffic mix;
from those names the files follow:

* configuration ``<c>``: its ``file`` entry, a ``config.json`` whose directory
  also holds ``model.py`` (the program and its shapes functions) and
  ``reference.py`` (the plain float32 reference);
* traffic mix ``<t>``: ``benchmark/traffic/<t>.json``, whose ``kind`` names the
  generator ``benchmark/traffic_kinds/<kind>.py``; a mix that names a ``base``
  mix is that mix with its own keys laid over it;
* loop ``<l>`` (named by the configuration): ``benchmark/loops/<l>.py``;
* per-layer metric ``<m>``: ``benchmark/layer_metrics/<m>.py``.

So a later PR adds a cell, configuration, mix or metric by adding files and an
entry to ``BENCHMARK.json``, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Registry:
    """The benchmark rooted at ``root`` (the checkout that holds
    ``BENCHMARK.json``), with its files under ``bench_dir``."""

    def __init__(self, root=ROOT, bench_dir=BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _entry(self, section, name):
        for e in self.spec[section]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.spec[section])
        raise KeyError(f"no {section} entry {name!r} in BENCHMARK.json "
                       f"(known: {known})")

    def cell(self, name):
        return self._entry("workloads", name)

    def config(self, name):
        """(configuration dict, its directory)."""
        path = os.path.join(self.root, self._entry("configs", name)["file"])
        with open(path) as f:
            return json.load(f), os.path.dirname(path)

    def mix(self, name):
        """The mix's parameters.  One that differs from another in a key or
        two (the same traffic under another layout) names it as ``base`` and
        states the difference alone, so the two cannot drift apart."""
        with open(os.path.join(self.bench_dir, "traffic", name + ".json")) as f:
            mix = json.load(f)
        base = mix.pop("base", None)
        return {**self.mix(base), **mix} if base else mix

    def metrics_of(self, section, cell_name):
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those without a ``workloads`` list, and those that list the cell."""
        return [m for m in self.spec[section]
                if cell_name in m.get("workloads", [cell_name])]

    def module(self, *relpath):
        """Import ``benchmark/<relpath>`` as a module of its own."""
        return load_module(os.path.join(self.bench_dir, *relpath))


def load_module(path):
    """Import the file at ``path`` under a name derived from the path (file
    names may hold dots, so this never goes through ``sys.path``)."""
    name = "benchfile_" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"benchmark file not found: {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod
