"""How a cell's name leads to its files. Nothing here knows a cell by name.

    workload  -> BENCHMARK.json "workloads" entry {name, config, traffic, chips}
    config    -> BENCHMARK.json "configs" entry -> its "file" (configs/<config>.json)
    traffic   -> benchmark/traffic/<traffic>.json
    query     -> benchmark/queries/<query>.sql (template) and <query>.py (reference)
    metric    -> benchmark/layer_metrics/<metric>.json (and <metric>.py if it says so)
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Query:
    """One query template with its plain reference."""

    def __init__(self, name):
        self.name = name
        with open(os.path.join(BENCH_DIR, "queries", name + ".sql")) as f:
            self.template = f.read()
        self.module = load_module(
            os.path.join(BENCH_DIR, "queries", name + ".py"),
            "benchmark_query_" + name)
        self.scans = self.module.SCANS          # {table: [columns the SQL names]}
        self.reference = self.module.reference  # (sf, params, lower) -> rows


class Cell:
    """A workload of BENCHMARK.json with everything its name resolves to."""

    def __init__(self, workload):
        self.bench = load_json(ROOT, "BENCHMARK.json")
        found = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not found:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.name = workload
        self.chips = found[0]["chips"]
        entry = [c for c in self.bench["configs"]
                 if c["name"] == found[0]["config"]][0]
        self.config = load_json(ROOT, entry["file"])
        self.traffic = load_json(BENCH_DIR, "traffic",
                                 found[0]["traffic"] + ".json")
        self.queries = {q["query"]: Query(q["query"])
                        for q in self.traffic["queries"]}

    def metrics(self, group):
        """The cell's metrics of "end_to_end" or "per_layer": a metric with no
        "workloads" key belongs to every cell."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]
