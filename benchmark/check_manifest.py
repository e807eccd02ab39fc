#!/usr/bin/env python3
"""Hold BENCHMARK.json to the rules a manifest is refused by, before the
driver reads it.  PR 22's whole benchmark was refused for one `layer` with a
space in it; this is run last before a benchmark PR finishes.

  python3 benchmark/check_manifest.py [path/to/BENCHMARK.json]

Exit 0 and "manifest ok", or exit 1 with every fault on a line of its own.
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# A configuration's file may name a world builder and a reference of its
# own (manifest.py): key -> (directory under paths[0], names the file exposes).
NAMED = {"world_builder": ("worlds", ("build_world", "to_program")),
         "reference": ("references", ("Reference",))}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
# Keys of a configuration that are widths (shapes): `reduced` may never
# name one.
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state_size|"
                   r"head_size|expansion|experts_per_tok")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s, what, faults, limit=200):
    if not (isinstance(s, str) and 1 <= len(s) <= limit
            and "\n" not in s and "\t" not in s):
        faults.append(f"{what}: must be 1 to {limit} characters on one "
                      f"line with no tab, not {s!r}")


def _exposes(path: str, names: tuple) -> list:
    """The names of `names` that the file does not bind at its top level
    (read, not imported: a world builder may import the program)."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    bound = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, ast.Assign):
            bound.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return [n for n in names if n not in bound]


def check_named(config: dict, what: str, home: str, under: str) -> list:
    """The faults of a configuration's `world_builder` / `reference` keys."""
    faults = []
    for key, (folder, names) in NAMED.items():
        if key not in config:
            continue
        name = config[key]
        if not (isinstance(name, str) and NAME.match(name)):
            faults.append(f"{what}: {key} {name!r} is not a name")
            continue
        path = os.path.join(home, folder, f"{name}.py")
        if not os.path.isfile(path):
            faults.append(f"{what}: {key} names {under}/{folder}/{name}.py, "
                          f"which is not there")
            continue
        try:
            missing = _exposes(path, names)
        except SyntaxError as e:
            faults.append(f"{what}: {under}/{folder}/{name}.py does not "
                          f"parse: {e}")
            continue
        if missing:
            faults.append(f"{what}: {under}/{folder}/{name}.py does not "
                          f"expose {missing}")
    if "world_builder" in config and "reference" not in config:
        faults.append(f"{what}: names a world builder and no reference: the "
                      f"default reference reads the default world only")
    return faults


def check(doc: dict, root: str) -> list:
    """-> the faults found (empty: the manifest stands)."""
    faults = []

    def name(s, what):
        if not (isinstance(s, str) and NAME.match(s)):
            faults.append(f"{what}: must be 1 to 64 characters from letters, "
                          f"digits, '_', '.' and '-', starting with a letter, "
                          f"digit or '_', not {s!r}")

    def keys(entry, group, what):
        want = KEYS[group]
        allowed = want | ({"workloads"} if group in ("end_to_end",
                                                     "per_layer") else set())
        if want - set(entry) or set(entry) - allowed:
            faults.append(f"{what}: keys must be {sorted(want)}, missing "
                          f"{sorted(want - set(entry))}, extra "
                          f"{sorted(set(entry) - allowed)}")

    if set(doc) != KEYS["top"]:
        faults.append(f"top level: keys must be exactly {sorted(KEYS['top'])}")
        return faults
    if len(json.dumps(doc)) > 64 * 1024:
        faults.append("the file is over 64 KiB")

    paths = doc["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
        return faults
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"paths: {p!r} is not a plain relative path")
        elif not os.path.isdir(os.path.join(root, p)):
            faults.append(f"paths: {p!r} is not a directory")
    cmd = doc["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        faults.append("command: a list of 1 to 32 strings")
    else:
        for word in cmd:
            _line(word, f"command word {word!r}", faults)
            if isinstance(word, str) and (word.startswith("/")
                                          or ".." in word.split("/")):
                faults.append(f"command: {word!r} starts with / or has ..")
            elif isinstance(word, str) and os.path.exists(
                    os.path.join(root, word)) and not any(
                        word == p or word.startswith(p + "/") for p in paths):
                faults.append(f"command: {word!r} is a file outside paths")
    rs = doc["run_seconds"]
    cells = doc["workloads"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= 51):
        faults.append(f"run_seconds: a whole number from 1 to 51, not {rs!r}")
    elif (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 > 43200:
        faults.append(f"run_seconds {rs}: a full check of 24 cells does not "
                      f"fit into 43200 s")

    def under_paths(f):
        return any(f.startswith(p + "/") for p in paths)

    configs = doc["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        faults.append("configs: 1 to 24")
    seen_files = set()
    for c in configs:
        what = f"config {c.get('name')!r}"
        keys(c, "configs", what)
        name(c.get("name"), what + " name")
        _line(c.get("source"), what + " source", faults)
        _line(c.get("why"), what + " why", faults)
        f = c.get("file", "")
        if not (isinstance(f, str) and PATH.match(f) and under_paths(f)
                and os.path.isfile(os.path.join(root, f))):
            faults.append(f"{what}: file {f!r} must exist under paths")
        elif f in seen_files:
            faults.append(f"{what}: file {f!r} is another configuration's")
        else:
            seen_files.add(f)
            try:
                with open(os.path.join(root, f)) as fh:
                    body = json.load(fh)
            except ValueError as e:
                faults.append(f"{what}: {f} is not JSON: {e}")
            else:
                faults += check_named(body if isinstance(body, dict) else {},
                                      what,
                                      os.path.join(root, paths[0]), paths[0])
        red = c.get("reduced")
        if not (isinstance(red, list) and len(red) <= 16):
            faults.append(f"{what}: reduced is a list of at most 16 keys")
        else:
            for k in red:
                name(k, what + f" reduced key {k!r}")
                if isinstance(k, str) and WIDTH.search(k):
                    faults.append(f"{what}: reduced names a width, {k!r}")
        if not any(w.get("config") == c.get("name") for w in cells):
            faults.append(f"{what}: no cell uses it")

    if not (isinstance(cells, list) and 1 <= len(cells) <= 24):
        faults.append("workloads: 1 to 24")
    pairs = set()
    for w in cells:
        what = f"workload {w.get('name')!r}"
        keys(w, "workloads", what)
        for k in ("name", "config", "traffic"):
            name(w.get(k), f"{what} {k}")
        _line(w.get("why"), what + " why", faults)
        if w.get("chips") not in (1, 4):
            faults.append(f"{what}: chips is 1 or 4")
        if w.get("config") not in [c.get("name") for c in configs]:
            faults.append(f"{what}: unknown config {w.get('config')!r}")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(f"{what}: config and traffic appear twice")
        pairs.add(pair)
        home = os.path.join(root, paths[0])
        mixes = [os.path.join(home, "traffic", f"{w.get('traffic')}{s}")
                 for s in TRAFFIC_SUFFIXES]
        mix = next((m for m in mixes if os.path.isfile(m)), None)
        if mix is None:
            faults.append(f"{what}: no traffic file {mixes[0]}")
        elif mix.endswith(".json"):
            with open(mix) as fh:
                gen = json.load(fh).get("generator", "")
            if not os.path.isfile(os.path.join(home, "generators",
                                               f"{gen}.py")):
                faults.append(f"{what}: traffic names generator {gen!r}, "
                              f"which {paths[0]}/generators/ does not hold")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        faults.append(f"{four} of {len(cells)} cells ask for 4 chips")

    names = [x.get("name") for g in ("configs", "workloads") for x in doc[g]]
    metrics = [m.get("name") for g in ("end_to_end", "per_layer")
               for m in doc[g]]
    for group in (names[:len(configs)], names[len(configs):], metrics):
        for n in set(group):
            if group.count(n) > 1:
                faults.append(f"the name {n!r} is used twice")

    cell_names = [w.get("name") for w in cells]
    e2e = doc["end_to_end"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        faults.append("end_to_end: 1 to 16 metrics")
    if not any(m.get("name") == "setup_s" for m in e2e):
        faults.append("end_to_end: one metric must be setup_s")

    def reported_in(m):
        return set(m.get("workloads", cell_names))

    def metric(m, group):
        what = f"{group} metric {m.get('name')!r}"
        keys(m, group, what)
        name(m.get("name"), what + " name")
        if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
            faults.append(f"{what}: unit must be 1 to 16 characters from "
                          f"letters, digits, '_', '/', '%', '.' and '-', "
                          f"not {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"{what}: better is lower or higher")
        if m.get("source") not in SOURCES:
            faults.append(f"{what}: source is one of {SOURCES}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                faults.append(f"{what}: workloads names no cell {c!r}")

    for m in e2e:
        metric(m, "end_to_end")
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"end_to_end metric {m.get('name')!r}: source is "
                          f"host_clock or device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.25):
            faults.append(f"end_to_end metric {m.get('name')!r}: bound is "
                          f"from 0.01 to 0.25, not {b!r}")
    layers = doc["per_layer"]
    if not (isinstance(layers, list) and 1 <= len(layers) <= 128):
        faults.append("per_layer: 1 to 128 metrics")
    for m in layers:
        metric(m, "per_layer")
        what = f"per_layer metric {m.get('name')!r}"
        name(m.get("layer"), what + " layer")
        moved = next((e for e in e2e if e.get("name") == m.get("moves")),
                     None)
        if moved is None:
            faults.append(f"{what}: moves {m.get('moves')!r} is no "
                          f"end-to-end metric")
        elif not reported_in(m) <= reported_in(moved):
            faults.append(f"{what}: reported in cells that do not report "
                          f"{m.get('moves')!r}")
        reader = os.path.join(root, paths[0], "layers", f"{m.get('name')}.py")
        if not os.path.isfile(reader):
            faults.append(f"{what}: no reader {reader}")
        if "roofline" in str(m.get("name")) and not (
                str(m.get("name")).endswith("_roofline")
                and m.get("unit") == "%"):
            faults.append(f"{what}: a roofline share is named "
                          f"<kernel>_roofline with the unit %")
    for c in cell_names:
        mine = [m for m in e2e if c in reported_in(m)]
        if not any(m.get("name") == "setup_s" for m in mine) or len(mine) < 2:
            faults.append(f"cell {c!r}: reports setup_s and at least one "
                          f"other end-to-end metric")
        if not any(c in reported_in(m) for m in layers):
            faults.append(f"cell {c!r}: reports no per-layer metric")
    return faults


def main(argv) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    path = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(here), "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    faults = check(doc, os.path.dirname(os.path.abspath(path)))
    for fault in faults:
        print(f"manifest: {fault}")
    if not faults:
        print("manifest ok")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
