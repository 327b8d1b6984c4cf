"""Loads ``BENCHMARK.json`` and the files it names, and checks them.

The check runs at the start of every run, so that a bad added file fails
before any chip time is spent: every file under ``configs/``,
``queries/``, ``traffic/``, ``end_to_end/`` and ``layer_metrics/`` must
parse, every name a cell or a mix refers to must exist, every family must
name fields of the dataset it is asked of, every ``moves`` must name an
end-to-end metric that the cell reports, and the names in
``BENCHMARK.json`` must be ones the driver takes.
"""

import importlib.util
import json
import os
import re

from . import readers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
LOOPS = ("open", "closed", "writer")
# the driver's rules for names in BENCHMARK.json; it refuses the file
# before any run where one is broken
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}")
LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class ManifestError(Exception):
    pass


def _load_dir(name):
    out = {}
    d = os.path.join(BENCH, name)
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        try:
            with open(os.path.join(d, fn)) as fh:
                out[fn[:-len(".json")]] = json.load(fh)
        except ValueError as e:
            raise ManifestError(f"{name}/{fn}: {e}") from None
    return out


def load_dataset(name):
    path = os.path.join(BENCH, "datasets", name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"datasets/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(
        "benchmark_dataset_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_fields(family):
    """The field names a family's parameters and meaning refer to; the
    ``{field}`` of a ``for_each_field`` family names none."""
    names = {spec["field"] for spec in family.get("params", {}).values()
             if "field" in spec}
    meaning = family.get("meaning", {})
    names |= {f for f, _, _ in meaning.get("filter", [])}
    agg = meaning.get("agg")
    if isinstance(agg, dict):
        names |= set(agg.get("groupby", []))
        names |= {agg["sum"]} if agg.get("sum") else set()
        names |= {agg["topn"][0]} if "topn" in agg else set()
    return {n for n in names if not n.startswith("{")}


class Manifest:
    def __init__(self):
        try:
            with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
                self.bench = json.load(fh)
        except (OSError, ValueError) as e:
            raise ManifestError(f"BENCHMARK.json: {e}") from None
        self.configs = _load_dir("configs")
        self.families = _load_dir("queries")
        self.mixes = _load_dir("traffic")
        self.readers = {**_load_dir("end_to_end"),
                        **_load_dir("layer_metrics")}
        self.cells = {w["name"]: w for w in self.bench["workloads"]}
        self._datasets = {}
        self._fields = None

    def dataset(self, name):
        """The dataset module ``datasets/<name>.py``, loaded once."""
        if name not in self._datasets:
            self._datasets[name] = load_dataset(name)
        return self._datasets[name]

    def _dataset_fields(self):
        """{dataset: its field names} for every dataset a configuration
        file names and ``datasets/`` holds."""
        if self._fields is None:
            names = {c.get("dataset", "") for c in self.configs.values()}
            self._fields = {
                n: {f["name"] for f in self.dataset(n).fields()}
                for n in sorted(names)
                if os.path.isfile(os.path.join(BENCH, "datasets",
                                               n + ".py"))}
        return self._fields

    def family_datasets(self, name):
        """The datasets a family is asked of: that of every configuration
        one of whose cells' mixes names it; for a family no cell's mix
        names, every dataset that holds the fields it names. Empty: the
        family can be asked of nothing the benchmark has."""
        fields = self._dataset_fields()
        named = {self.configs[w["config"]].get("dataset")
                 for w in self.cells.values()
                 if w["config"] in self.configs
                 and name in self.mix_families(
                     self.mixes.get(w["traffic"], {}))}
        if named:
            return sorted(named & set(fields))
        need = family_fields(self.families[name])
        return [d for d, have in fields.items() if need <= have]

    def metrics(self, cell, group):
        """The ``group`` ("end_to_end" | "per_layer") entries this cell
        reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or cell in m["workloads"]]

    def mix_families(self, mix):
        """Every family name a mix refers to."""
        names = set(mix.get("families", {})) | set(mix.get("round", []))
        names |= set(mix.get("readback", [])) | set(mix.get("final", []))
        names |= set(mix.get("side_reads", {}).get("families", {}))
        return names

    def check(self):
        problems = []
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in self.bench[group]:
                if not NAME.fullmatch(entry["name"]):
                    problems.append(f"{group}: {entry['name']!r} is not "
                                    f"a plain name")
                if len(entry.get("why", "")) > 200:
                    problems.append(f"{group}: the why of {entry['name']} "
                                    f"has over 200 characters")
        for m in self.bench["per_layer"]:
            if not LAYER.fullmatch(m["layer"]):
                problems.append(f"per_layer {m['name']}: layer "
                                f"{m['layer']!r} is not a plain name")
        for name, fam in self.families.items():
            for key in ("text", "meaning"):
                if key not in fam:
                    problems.append(f"queries/{name}.json: no {key!r}")
            if fam.get("name") != name:
                problems.append(f"queries/{name}.json: name is "
                                f"{fam.get('name')!r}")
        fields = self._dataset_fields()
        for name, fam in self.families.items():
            if not self.family_datasets(name):
                problems.append(
                    f"queries/{name}.json: no cell's mix names it and no "
                    f"dataset holds its fields "
                    f"{sorted(family_fields(fam))}")
        for cell, w in self.cells.items():
            dataset = self.configs.get(w["config"], {}).get("dataset")
            mix = self.mixes.get(w["traffic"], {})
            for name in sorted(self.mix_families(mix) & set(self.families)):
                lacks = (family_fields(self.families[name])
                         - fields.get(dataset, set()))
                if dataset in fields and lacks:
                    problems.append(
                        f"cell {cell}: queries/{name}.json names "
                        f"{sorted(lacks)}, which datasets/{dataset}.py "
                        f"does not have")
        for name, spec in self.readers.items():
            if spec.get("kind") not in readers.KINDS:
                problems.append(f"metric reader {name}: unknown kind "
                                f"{spec.get('kind')!r}")
        for name, mix in self.mixes.items():
            if mix.get("loop") not in LOOPS:
                problems.append(f"traffic/{name}.json: loop must be one "
                                f"of {LOOPS}")
            for fam in sorted(self.mix_families(mix) - set(self.families)):
                problems.append(f"traffic/{name}.json: no queries/"
                                f"{fam}.json")
        for cfg in self.bench["configs"]:
            if cfg["name"] not in self.configs:
                problems.append(f"no configs/{cfg['name']}.json")
            elif not os.path.isfile(os.path.join(
                    BENCH, "datasets",
                    self.configs[cfg["name"]].get("dataset", "") + ".py")):
                problems.append(f"configs/{cfg['name']}.json: its dataset "
                                f"has no file under datasets/")
        for cell, w in self.cells.items():
            if w["config"] not in self.configs:
                problems.append(f"cell {cell}: no configs/{w['config']}.json")
            if w["traffic"] not in self.mixes:
                problems.append(f"cell {cell}: no traffic/{w['traffic']}.json")
            e2e = {m["name"] for m in self.metrics(cell, "end_to_end")}
            for m in (self.metrics(cell, "end_to_end")
                      + self.metrics(cell, "per_layer")):
                if m["name"] not in self.readers:
                    problems.append(f"metric {m['name']}: no reader file")
            for m in self.metrics(cell, "per_layer"):
                if m["moves"] not in e2e:
                    problems.append(
                        f"cell {cell}: {m['name']} moves {m['moves']}, "
                        f"which the cell does not report")
        if problems:
            raise ManifestError("; ".join(problems))
