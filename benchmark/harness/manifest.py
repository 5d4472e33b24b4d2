"""BENCHMARK.json and the files its names resolve to.

Everything that belongs to one configuration, one traffic mix, one entry or
one per-layer metric is a file of its own, found by name under the
directories listed in ``paths``; a later change adds files and entries to
``BENCHMARK.json`` and edits nothing here:

    <path>/configs/<config>.json        (named by the entry's ``file``)
    <path>/references/<config>.py       (or the config's ``reference`` key)
    <path>/traffic/<traffic>.json       parameters; names its ``entry``
    <path>/entries/<entry>.py           adapter that drives one program path
    <path>/layer_metrics/<metric>.py    reader from observations to a number
"""

import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError("%s: %s" % (path, e)) from None


def load_module(path):
    """Import one file by path.  Its directory joins ``sys.path`` so that a
    reference finds the plain layers beside it."""
    if not os.path.isfile(path):
        raise ManifestError("no such file: %s" % path)
    here = os.path.dirname(path)
    if here not in sys.path:
        sys.path.insert(0, here)
    spec = importlib.util.spec_from_file_location(
        "benchfile_" + re.sub(r"\W", "_", path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.paths = list(self.data["paths"])

    def find(self, kind, name, ext):
        """``<path>/<kind>/<name><ext>`` under the first path that has it."""
        for p in self.paths:
            candidate = os.path.join(self.root, p, kind, name + ext)
            if os.path.isfile(candidate):
                return candidate
        raise ManifestError("no %s/%s%s under %s"
                            % (kind, name, ext, self.paths))

    def named(self, section, name):
        for item in self.data[section]:
            if item["name"] == name:
                return item
        raise ManifestError("BENCHMARK.json has no %s named %r (has: %s)"
                            % (section, name,
                               [i["name"] for i in self.data[section]]))

    def cell(self, name):
        return Cell(self, self.named("workloads", name))

    def metrics(self, section, cell_name):
        """The metrics of ``section`` that ``cell_name`` reports."""
        return [m for m in self.data[section]
                if "workloads" not in m or cell_name in m["workloads"]]


class Cell:
    """One workload with its configuration, traffic parameters, entry and
    reference resolved."""

    def __init__(self, manifest, workload):
        self.manifest = manifest
        self.name = workload["name"]
        self.chips = int(workload["chips"])
        cfg_entry = manifest.named("configs", workload["config"])
        self.config_name = cfg_entry["name"]
        self.config = _load_json(os.path.join(manifest.root,
                                              cfg_entry["file"]))
        self.traffic_name = workload["traffic"]
        self.traffic = _load_json(
            manifest.find("traffic", workload["traffic"], ".json"))
        self.end_to_end = manifest.metrics("end_to_end", self.name)
        self.per_layer = manifest.metrics("per_layer", self.name)

    def entry(self):
        return load_module(self.manifest.find(
            "entries", self.traffic["entry"], ".py"))

    def reference(self):
        explicit = self.config.get("reference")
        path = os.path.join(self.manifest.root, explicit) if explicit \
            else self.manifest.find("references", self.config_name, ".py")
        return load_module(path)

    def reader(self, metric_name):
        return load_module(self.manifest.find(
            "layer_metrics", metric_name, ".py"))
