"""Reads ``BENCHMARK.json`` and the data files it names, and checks both.

Everything that belongs to one cell is data: the manifest's entry, the cell's
file under ``workloads/``, its configuration's file under ``configs/`` and
its traffic mix's file under ``traffic/``.  A later PR adds a cell by adding
such files and entries; nothing here knows a cell by name.
"""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(path=MANIFEST):
    return _load(path)


def _line(text, what):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ManifestError("%s: wants 1-200 characters on one line" % what)


def validate(manifest, bench_dir=HERE):
    """The contract's rules that can be checked without a run; raises
    :class:`ManifestError` naming the first breach."""
    def name_ok(n, what):
        if not (isinstance(n, str) and _NAME.match(n)):
            raise ManifestError("%s: bad name %r" % (what, n))

    configs = {}
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        if c["name"] in configs:
            raise ManifestError("config %s twice" % c["name"])
        _line(c["source"], "config %s source" % c["name"])
        _line(c["why"], "config %s why" % c["name"])
        if not os.path.isfile(os.path.join(os.path.dirname(bench_dir),
                                           c["file"])):
            raise ManifestError("config %s: no file %s"
                                % (c["name"], c["file"]))
        for key in c["reduced"]:
            name_ok(key, "config %s reduced" % c["name"])
        configs[c["name"]] = c

    seen = set()

    def metric_ok(m, sources):
        name_ok(m["name"], "metric")
        if m["name"] in seen:
            raise ManifestError("metric %s twice" % m["name"])
        seen.add(m["name"])
        if not _UNIT.match(m["unit"]):
            raise ManifestError("metric %s: bad unit %r"
                                % (m["name"], m["unit"]))
        if m["better"] not in ("lower", "higher"):
            raise ManifestError("metric %s: better?" % m["name"])
        if m["source"] not in sources:
            raise ManifestError("metric %s: source %r is not one of %s"
                                % (m["name"], m["source"], sources))

    e2e = {}
    for m in manifest["end_to_end"]:
        # an end-to-end metric is taken by the benchmark itself
        metric_ok(m, ("host_clock", "device_trace"))
        if not 0 < m["bound"] <= 0.1:
            raise ManifestError("metric %s: bound %r" % (m["name"],
                                                         m["bound"]))
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        raise ManifestError("no setup_s among the end-to-end metrics")

    cells, pairs = {}, set()
    for w in manifest["workloads"]:
        for key in ("name", "config", "traffic"):
            name_ok(w[key], "workload " + key)
        _line(w["why"], "workload %s why" % w["name"])
        if w["name"] in cells:
            raise ManifestError("workload %s twice" % w["name"])
        if w["config"] not in configs:
            raise ManifestError("workload %s: unknown config %s"
                                % (w["name"], w["config"]))
        if w["chips"] not in (1, 4):
            raise ManifestError("workload %s: chips %r"
                                % (w["name"], w["chips"]))
        if (w["config"], w["traffic"]) in pairs:
            raise ManifestError("pair %s/%s twice"
                                % (w["config"], w["traffic"]))
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        load_cell(w["name"], manifest, bench_dir)    # its files are there
    used = {w["config"] for w in cells.values()}
    if used != set(configs):
        raise ManifestError("configs used by no cell: %s"
                            % sorted(set(configs) - used))
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise ManifestError("%d of %d cells ask for four chips"
                            % (four, len(cells)))

    for m in manifest["per_layer"]:
        metric_ok(m, _SOURCES)
        _line(m["layer"], "metric %s layer" % m["name"])
        if m["moves"] not in e2e:
            raise ManifestError("metric %s moves %r, which is no "
                                "end-to-end metric" % (m["name"], m["moves"]))
        for cell in m.get("workloads", cells):
            if cell not in cells:
                raise ManifestError("metric %s: unknown cell %s"
                                    % (m["name"], cell))
            if m["moves"] not in metrics_of(manifest, "end_to_end", cell):
                raise ManifestError(
                    "metric %s moves %s, which cell %s does not report"
                    % (m["name"], m["moves"], cell))
        reader = os.path.join(bench_dir, "layer_metrics", m["name"] + ".py")
        if not os.path.isfile(reader):
            raise ManifestError("metric %s: no reader %s"
                                % (m["name"], reader))
    for cell in cells:
        if not metrics_of(manifest, "per_layer", cell):
            raise ManifestError("cell %s reports no per-layer metric" % cell)
        if len(metrics_of(manifest, "end_to_end", cell)) < 2:
            raise ManifestError("cell %s reports only setup_s" % cell)
    return manifest


def metrics_of(manifest, group, cell):
    """The metrics of ``group`` that ``cell`` reports, in manifest order:
    those with no ``workloads`` key and those that list the cell."""
    return {m["name"]: m for m in manifest[group]
            if cell in m.get("workloads", (cell,))}


def load_cell(name, manifest=None, bench_dir=HERE):
    """The cell's four pieces of data: its manifest entry joined with
    ``workloads/<name>.json`` (which holds what the entry may not: the
    program's switches and what the compiled step must show, and repeats
    nothing of the entry), its configuration file and its traffic file."""
    manifest = manifest or load_manifest()
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise ManifestError("no workload %r in the manifest (it has: %s)" % (
            name, ", ".join(w["name"] for w in manifest["workloads"])))
    workload = _load(os.path.join(bench_dir, "workloads", name + ".json"))
    twice = sorted(set(workload) & set(entries[0]))
    if twice:
        raise ManifestError("workloads/%s.json repeats %s of its manifest "
                            "entry" % (name, ", ".join(twice)))
    workload.update(entries[0])
    by_name = {c["name"]: c for c in manifest["configs"]}
    config = _load(os.path.join(os.path.dirname(bench_dir),
                                by_name[workload["config"]]["file"]))
    traffic = _load(os.path.join(bench_dir, "traffic",
                                 workload["traffic"] + ".json"))
    return {"workload": workload, "config": config, "traffic": traffic}


def load_by_name(kind, name, bench_dir=HERE):
    """The module ``<kind>/<name>.py`` (a builder, a traffic driver, a FLOP
    model or a per-layer reader), found by the name the data gives.  Names
    may hold dots, so this goes by path and not through ``import``."""
    import importlib.util

    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError("no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind, re.sub(r"\W", "_", name)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
