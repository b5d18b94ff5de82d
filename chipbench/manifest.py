"""Finds everything by the names in BENCHMARK.json: a cell's file, its
configuration's file, each metric's file and reader, each family's
model and reference modules. Adding any of them is new files and new
entries; nothing here lists a name."""
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """BENCHMARK.json and the files it names, read from `root`. The
    fixture manifest of the tests lives under chipbench/testdata with
    the same layout, so `base` is where workloads/ and metrics/ are."""

    def __init__(self, path=None, base=None):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = os.path.dirname(os.path.abspath(self.path))
        self.base = base or HERE
        self.doc = _load(self.path)
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def cell(self, name):
        """The manifest's entry merged over the cell's own file."""
        if name not in self.cells:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"{self.path}; there are {sorted(self.cells)}")
        entry = self.cells[name]
        spec = _load(os.path.join(self.base, "workloads", name + ".json"))
        for key in ("config", "chips"):
            if spec[key] != entry[key]:
                raise SystemExit(
                    f"chipbench: {name}.json says {key}={spec[key]!r}, "
                    f"BENCHMARK.json says {entry[key]!r}")
        return dict(spec, name=name, traffic=entry["traffic"])

    def config(self, name):
        return _load(os.path.join(self.root, self.configs[name]["file"]))

    def metrics_of(self, cell_name, traced):
        """The metric entries this cell reports in this kind of run,
        each merged over its own file (reader, args)."""
        cell_e2e = [m for m in self.end_to_end.values()
                    if cell_name in m.get("workloads", [cell_name])]
        if not traced:
            chosen = cell_e2e
        else:
            reported = {m["name"] for m in cell_e2e}
            chosen = [m for m in self.per_layer.values()
                      if cell_name in m.get("workloads", [cell_name])
                      and m["moves"] in reported]
        return [dict(_load(os.path.join(HERE, "metrics",
                                        m["name"] + ".json")), **m)
                for m in chosen]


def family(name):
    """(model module, reference module) of a configuration's family."""
    return (importlib.import_module(f"chipbench.models.{name}"),
            importlib.import_module(f"chipbench.reference.{name}"))


def reader(name):
    return importlib.import_module(f"chipbench.readers.{name}")


def peaks(device_kind):
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(
            f"chipbench: no published peak for device kind "
            f"{device_kind!r} in peaks.json; a device that is not in "
            f"the table is an error, not a default")
    return table["devices"][device_kind]
