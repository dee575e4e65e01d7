"""``BENCHMARK.json`` and the files its names point at.

The harness is driven by data: a cell names a configuration and a traffic
mix; each is a file found by that name, each metric is a reader found by
its name, and what the harness knows about a configuration's block type
(its plain reference, its arithmetic, the scope names it adds) is a module
under ``blocks/`` found by the name the configuration's file gives.
``validate`` holds the manifest to the contract's limits (the ones a file
can be checked for without a run), so a later PR that adds an entry learns
of a slip from tier-1 and not from a refused chip run.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
#: what a block module must define (``blocks/dense.py`` says what each is);
#: ``SCOPES`` and ``PUBLISHED_TO_FIELD`` are its to add
BLOCK_EXPORTS = ("logits", "loss", "matmul_params")


class ManifestError(ValueError):
    pass


def load(root: str = CHECKOUT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {[w['name'] for w in manifest['workloads']]})")


def resolve(manifest: dict, name: str, root: str = CHECKOUT) -> dict:
    """Everything one cell runs on, found by name: the manifest entry, the
    configuration's file and the block module it names, the traffic mix's
    file, the cell's own file."""
    w = cell(manifest, name)
    cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    config = _read_json(os.path.join(root, cfg["file"]))
    return {
        "cell": w,
        "config_entry": cfg,
        "config": config,
        "block": find_block(bench_dir, config, cfg["file"]),
        "traffic": _read_json(os.path.join(bench_dir, "traffic",
                                           w["traffic"] + ".json")),
        "workload": _read_json(os.path.join(bench_dir, "workloads",
                                            w["name"] + ".json")),
        "bench_dir": bench_dir,
    }


def find_module(bench_dir: str, group_dir: str, name: str):
    """The module ``<bench_dir>/<group_dir>/<name>.py``: how a metric's
    reader, a traffic generator and a configuration's block are found by
    the name the data gives."""
    path = os.path.join(bench_dir, group_dir, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {group_dir}/{name}.py under {bench_dir}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{group_dir}.{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_block(bench_dir: str, config: dict, where: str):
    """The module ``<bench_dir>/blocks/<block>.py`` that the configuration
    names under ``block``, held to what the harness calls on it."""
    name = config.get("block")
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(f"{where}: names no block (\"block\": the name "
                            f"of a file under blocks/)")
    module = find_module(bench_dir, "blocks", name)
    missing = [f for f in BLOCK_EXPORTS
               if not callable(getattr(module, f, None))]
    if missing:
        raise ManifestError(f"blocks/{name}.py defines no "
                            f"{', '.join(missing)}")
    return module


def metrics_for(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` ('end_to_end' | 'per_layer') this cell
    reports: those without a ``workloads`` list, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def _one_line(s, what):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        raise ManifestError(f"{what}: 1..200 characters on one line")


def validate(manifest: dict, root: str = CHECKOUT) -> None:
    """Raise ManifestError on the first breach of the contract's static
    limits (keys, names, units, counts, files and block modules found by
    name, the share of four-chip cells, what a run's length lets a full
    check cost)."""
    if set(manifest) != TOP_KEYS:
        raise ManifestError(f"top-level keys {sorted(manifest)} != "
                            f"{sorted(TOP_KEYS)}")
    paths, cmd = manifest["paths"], manifest["command"]
    if not 1 <= len(paths) <= 16 or not all(PATH.match(p) for p in paths):
        raise ManifestError("paths: 1..16 relative directories")
    for p in paths:
        if p.startswith("/") or ".." in p.split("/"):
            raise ManifestError(f"path {p!r} leaves the repo")
        if not os.path.isdir(os.path.join(root, p)):
            raise ManifestError(f"path {p!r} is not a directory")
    if not 1 <= len(cmd) <= 32:
        raise ManifestError("command: 1..32 strings")
    for word in cmd:
        _one_line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command word {word!r} leaves the repo")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        raise ManifestError("run_seconds: a whole number in 1..51")
    if (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 > 43200:
        raise ManifestError("run_seconds: a full check of 24 cells would "
                            "not fit 43200 s")

    def under_paths(f):
        return any(f == p or f.startswith(p + "/") for p in paths)

    names = set()
    files = set()
    cfgs = manifest["configs"]
    if not 1 <= len(cfgs) <= 24:
        raise ManifestError("configs: 1..24")
    for c in cfgs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config keys {sorted(c)}")
        if not NAME.match(c["name"]) or c["name"] in names:
            raise ManifestError(f"config name {c['name']!r}")
        names.add(c["name"])
        _one_line(c["source"], "config source")
        _one_line(c["why"], "config why")
        if not PATH.match(c["file"]) or not under_paths(c["file"]) \
                or c["file"] in files:
            raise ManifestError(f"config file {c['file']!r}")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            raise ManifestError(f"config {c['name']}: reduced")
        for k in c["reduced"]:
            if k.endswith(("_dim", "_rank")) or any(
                    w in k for w in ("hidden_size", "intermediate_size",
                                     "latent", "state_size", "proj",
                                     "head_size", "head_dim", "expan",
                                     "experts_per_tok")):
                raise ManifestError(f"config {c['name']}: {k!r} is a width")
        body = _read_json(os.path.join(root, c["file"]))
        if not isinstance(body, dict):
            raise ManifestError(f"{c['file']}: not a JSON object")

    cells = manifest["workloads"]
    if not 2 <= len(cells) <= 24:
        raise ManifestError("workloads: 2..24")
    pairs, cell_names = set(), set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            if not NAME.match(w[k]):
                raise ManifestError(f"workload {k} {w[k]!r}")
        if w["name"] in cell_names or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"workload {w['name']!r} appears twice")
        cell_names.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        if w["config"] not in names:
            raise ManifestError(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips 1 or 4")
        _one_line(w["why"], "workload why")
        # every file by name, the configuration's block module among them
        info = resolve(manifest, w["name"], root)
        gen = os.path.join(info["bench_dir"], "traffic",
                           str(info["traffic"].get("generator")) + ".py")
        if not os.path.isfile(gen):
            raise ManifestError(f"workload {w['name']}: no generator {gen}")
    used = {w["config"] for w in cells}
    if used != names:
        raise ManifestError(f"configs no cell uses: {sorted(names - used)}")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        raise ManifestError(f"{four} four-chip cells of {len(cells)}")

    metric_names = set()
    e2e = manifest["end_to_end"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(manifest["per_layer"]) <= 128:
        raise ManifestError("end_to_end: 1..16, per_layer: 1..128")
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in manifest[group]:
            if set(m) - {"workloads"} != keys:
                raise ManifestError(f"{group} keys {sorted(m)}")
            if not NAME.match(m["name"]) or m["name"] in metric_names:
                raise ManifestError(f"metric name {m['name']!r}")
            metric_names.add(m["name"])
            if not UNIT.match(m["unit"]):
                raise ManifestError(f"{m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better")
            if m["source"] not in SOURCES:
                raise ManifestError(f"{m['name']}: source")
            for wl in m.get("workloads", []):
                if wl not in cell_names:
                    raise ManifestError(f"{m['name']}: unknown cell {wl!r}")
            reader = os.path.join(
                root, paths[0],
                "end_to_end" if group == "end_to_end" else "layer_metrics",
                m["name"] + ".py")
            if not os.path.isfile(reader):
                raise ManifestError(f"{m['name']}: no reader {reader}")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise ManifestError("end_to_end needs setup_s")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: end-to-end source")
        if not 0.01 <= m["bound"] <= 0.1:
            raise ManifestError(f"{m['name']}: bound in 0.01..0.1")
    for m in manifest["per_layer"]:
        _one_line(m["layer"], "layer")
        if m["moves"] not in e2e_names:
            raise ManifestError(f"{m['name']}: moves {m['moves']!r}")
    for w in cells:
        mine = {m["name"] for m in metrics_for(manifest, "end_to_end",
                                               w["name"])}
        if "setup_s" not in mine or len(mine) < 2:
            raise ManifestError(f"cell {w['name']}: setup_s and one more")
        layer = metrics_for(manifest, "per_layer", w["name"])
        if not layer:
            raise ManifestError(f"cell {w['name']}: no per-layer metric")
        for m in layer:
            if m["moves"] not in mine:
                raise ManifestError(f"cell {w['name']}: {m['name']} moves "
                                    f"{m['moves']}, not reported there")
