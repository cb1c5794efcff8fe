"""Find everything that belongs to a cell by the names in BENCHMARK.json.

Nothing here is a table: a configuration, a traffic mix, a driver, a
reference, a count module, a check, a per-layer metric and a cell's
limits are each one file whose name is the name the manifest (or the
configuration's file) gives.  A later PR adds files and entries and
edits nothing that is there.
"""
import importlib.util
import json
import os


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import one file by path under a name of its own."""
    if not os.path.isfile(path):
        raise FileNotFoundError("perfbench: no such file %s" % path)
    rel = os.path.splitext(os.path.basename(path))[0]
    parent = os.path.basename(os.path.dirname(path))
    name = "perfbench_%s_%s" % (parent, rel.replace("-", "_"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, bench, entry):
        self.bench = bench
        self.name = entry["name"]
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.chips = int(entry["chips"])
        cfg_entry = bench.config_entry(self.config_name)
        self.config = _load_json(os.path.join(bench.root, cfg_entry["file"]))
        self.traffic = _load_json(bench.path(
            "traffic", self.traffic_name + ".json"))

    def config_for(self, rehearse=False):
        """The configuration as run; ``rehearse`` overlays the file's own
        ``rehearse`` sizes (tiny, CPU)."""
        cfg = dict(self.config)
        if rehearse:
            cfg.update(cfg.get("rehearse", {}))
        return cfg

    def driver(self):
        return load_module(self.bench.path(
            "drivers", self.config["driver"] + ".py"))

    def reference(self):
        return load_module(self.bench.path(
            "reference", self.config["family"] + ".py"))

    def counts(self):
        return load_module(self.bench.path(
            "counts", self.config["family"] + ".py"))

    def check(self):
        return load_module(self.bench.path(
            "checks", self.config["check"] + ".py"))

    def limits(self):
        return _load_json(self.bench.path("limits", self.name + ".json"))

    def _metrics(self, group):
        out = []
        for spec in self.bench.manifest[group]:
            if "workloads" not in spec or self.name in spec["workloads"]:
                out.append(spec)
        return out

    def end_to_end_metrics(self):
        return self._metrics("end_to_end")

    def per_layer_metrics(self):
        return self._metrics("per_layer")


class Bench:
    def __init__(self, root):
        self.root = root
        self.dir = os.path.join(root, "perfbench")
        self.manifest = _load_json(os.path.join(root, "BENCHMARK.json"))

    def path(self, *parts):
        return os.path.join(self.dir, *parts)

    def config_entry(self, name):
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return c
        raise KeyError("perfbench: BENCHMARK.json has no config %r" % name)

    def cell(self, name):
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return Cell(self, w)
        raise KeyError("perfbench: BENCHMARK.json has no workload %r; it "
                       "has %s" % (name, [w["name"] for w in
                                          self.manifest["workloads"]]))

    def peaks(self, device_kind, rehearse=False):
        """The chip's published peaks; an unknown device is an error
        (``--rehearse`` gets none: no share of a peak is ever computed
        from a CPU run)."""
        if rehearse:
            return None
        table = _load_json(self.path("peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError("perfbench: device kind %r is not in peaks.json "
                           "(%s)" % (device_kind, sorted(table)))
        return table[device_kind]

    def metric_reader(self, name):
        return load_module(self.path("metrics", name + ".py"))
