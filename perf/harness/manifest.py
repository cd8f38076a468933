"""Find a cell's data and code by the names in the manifest.

The harness is driven by data: a configuration, a traffic mix, a
generator, a system runner, a reference and a metric reader are files of
their own under perf/, found by name, so a later PR adds files plus one
appended manifest entry and edits nothing that is there.
"""
import importlib.util
import json
import os

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)


def load_json(relpath):
    with open(os.path.join(ROOT, relpath)) as f:
        return json.load(f)


def load_plugin(kind, name):
    """The module perf/<kind>/<name>.py (names need not be identifiers)."""
    path = os.path.join(PERF_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {kind[:-1] if kind.endswith('s') else kind} named "
            f"{name!r}: {os.path.relpath(path, ROOT)} does not exist")
    mod_name = f"perf_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of the manifest's `workloads` with its configuration and
    traffic files loaded, and the metrics that apply to it."""

    def __init__(self, manifest, name):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if name not in by_name:
            raise KeyError(
                f"no workload {name!r} in the manifest (have "
                f"{sorted(by_name)})")
        w = by_name[name]
        self.name = name
        self.chips = int(w["chips"])
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == w["config"])
        self.config_name = w["config"]
        self.config = load_json(cfg_entry["file"])
        self.traffic_name = w["traffic"]
        self.traffic = load_json(f"perf/traffic/{w['traffic']}.json")
        self.end_to_end = _applicable(manifest["end_to_end"], name)
        names = {m["name"] for m in self.end_to_end}
        # a per-layer metric is reported only where the metric it moves is
        self.per_layer = [m for m in _applicable(manifest["per_layer"], name)
                          if m["moves"] in names]


def _applicable(metrics, workload):
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]
