#!/usr/bin/env python3
"""check_manifest.py - hold BENCHMARK.json to its contract before anything runs.

`python3 benchmark/check_manifest.py [BENCHMARK.json]` prints every fault it
finds and exits non-zero if there is one. `check(manifest, root)` returns the
list of faults, so tests and `run.py` use the same rules. The rules are the
driver's, as the builder's instructions state them: the character sets of
names, units and layers (PR 22 was refused for a layer named in plain words),
the keys each entry may have, the cells each metric lists, the end-to-end
metric each per-layer metric moves, the share of four-chip cells, and the
files a cell needs. Imports nothing but the standard library.
"""
import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# a key `reduced` may never name: a width of the model
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|_dim$|"
                   r"_rank$|expansion|experts_per_tok|n_embd|n_inner|d_model|"
                   r"d_ff|n_features|max_bin|max_depth|num_leaves)")

KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
MAX_CELLS = 24
MAX_RUN_SECONDS = 51


def _line(text, what, faults):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text and "\r" not in text):
        faults.append(f"{what}: must be 1 to 200 characters on one line, "
                      f"with no tab")


def _name(text, what, faults):
    if not (isinstance(text, str) and NAME.match(text)):
        faults.append(
            f"{what}: {text!r} must be 1 to 64 characters from letters, "
            f"digits, '_', '.' and '-', starting with a letter, digit or '_'")


def _keys(entry, kind, what, faults, optional=()):
    if not isinstance(entry, dict):
        faults.append(f"{what}: must be an object")
        return False
    want = KEYS[kind]
    missing = want - set(entry)
    extra = set(entry) - want - set(optional)
    if missing:
        faults.append(f"{what}: missing keys {sorted(missing)}")
    if extra:
        faults.append(f"{what}: keys {sorted(extra)} are not allowed")
    return not missing


def _inside(path, paths):
    norm = os.path.normpath(path)
    return any(norm == p or norm.startswith(p.rstrip("/") + "/")
               for p in map(os.path.normpath, paths))


def check(manifest, root=None):
    """Every fault of `manifest` (a dict) against the contract; files are
    looked up under `root` when it is given."""
    faults = []
    if not isinstance(manifest, dict):
        return ["the manifest must be a JSON object"]
    if len(json.dumps(manifest)) > 64 * 1024:
        faults.append("the manifest is over 64 KiB")
    if set(manifest) != KEYS["top"]:
        faults.append(f"top level must have exactly the keys "
                      f"{sorted(KEYS['top'])}, has {sorted(manifest)}")
        return faults

    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            faults.append(f"paths: {p!r} is not a relative path of letters, "
                          f"digits, '_', '.', '-' and '/'")
        elif root and not os.path.isdir(os.path.join(root, p)):
            faults.append(f"paths: {p!r} is not a directory")

    command = manifest["command"]
    if not (isinstance(command, list) and 1 <= len(command) <= 32):
        faults.append("command: a list of 1 to 32 strings")
        command = []
    for word in command:
        _line(word, f"command word {word!r}", faults)
        if isinstance(word, str) and (word.startswith("/")
                                      or ".." in word.split("/")):
            faults.append(f"command: {word!r} leaves the repo")
        elif isinstance(word, str) and root and "/" in word \
                and os.path.exists(os.path.join(root, word)) \
                and not _inside(word, paths):
            faults.append(f"command: {word!r} is a file outside paths")

    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= MAX_RUN_SECONDS):
        faults.append(f"run_seconds: a whole number from 1 to "
                      f"{MAX_RUN_SECONDS}")

    # ---- configurations
    configs = manifest["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        faults.append("configs: 1 to 24 entries")
        configs = []
    config_names, files = [], []
    for c in configs:
        what = f"config {c.get('name') if isinstance(c, dict) else c!r}"
        if not _keys(c, "config", what, faults):
            continue
        _name(c["name"], what, faults)
        config_names.append(c["name"])
        _line(c["source"], f"{what} source", faults)
        _line(c["why"], f"{what} why", faults)
        f = c["file"]
        if not (isinstance(f, str) and PATH.match(f) and _inside(f, paths)):
            faults.append(f"{what}: file {f!r} must lie under paths")
        elif f in files:
            faults.append(f"{what}: file {f!r} is another configuration's")
        elif root:
            try:
                with open(os.path.join(root, f)) as fh:
                    if not isinstance(json.load(fh), dict):
                        faults.append(f"{what}: {f} is not a JSON object")
            except (OSError, ValueError) as e:
                faults.append(f"{what}: cannot read {f}: {e}")
        files.append(f)
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16):
            faults.append(f"{what}: reduced is a list of at most 16 keys")
            red = []
        for key in red:
            _name(key, f"{what} reduced key", faults)
            if isinstance(key, str) and WIDTH.search(key):
                faults.append(f"{what}: reduced may not name a width "
                              f"({key!r})")
    _unique(config_names, "configuration", faults)

    # ---- cells
    cells = manifest["workloads"]
    if not (isinstance(cells, list) and 1 <= len(cells) <= MAX_CELLS):
        faults.append(f"workloads: 1 to {MAX_CELLS} cells")
        cells = []
    cell_names, pairs, used = [], [], set()
    for w in cells:
        what = f"cell {w.get('name') if isinstance(w, dict) else w!r}"
        if not _keys(w, "workload", what, faults):
            continue
        _name(w["name"], what, faults)
        _name(w["traffic"], f"{what} traffic", faults)
        _name(w["config"], f"{what} config", faults)
        _line(w["why"], f"{what} why", faults)
        cell_names.append(w["name"])
        if w["config"] not in config_names:
            faults.append(f"{what}: no configuration {w['config']!r}")
        used.add(w["config"])
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            faults.append(f"{what}: chips must be 1 or 4")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            faults.append(f"{what}: the pair {pair} appears twice")
        pairs.append(pair)
        if root and paths and isinstance(w["traffic"], str) and not any(
                os.path.isfile(os.path.join(root, p, "traffic",
                                            w["traffic"] + ".json"))
                for p in paths):
            faults.append(f"{what}: no data file traffic/{w['traffic']}"
                          f".json under paths")
    _unique(cell_names, "cell", faults)
    for name in config_names:
        if name not in used:
            faults.append(f"config {name}: no cell uses it")
    four = sum(1 for w in cells if isinstance(w, dict)
               and w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} of {len(cells)} cells ask for 4 chips; at "
                      f"most 25% rounded down (and one always) may")

    # ---- metrics
    def listed(metric, what):
        """The cells a metric is reported in."""
        if "workloads" not in metric:
            return list(cell_names)
        ws = metric["workloads"]
        if not (isinstance(ws, list) and ws):
            faults.append(f"{what}: workloads must be a non-empty list")
            return []
        for w in ws:
            if w not in cell_names:
                faults.append(f"{what}: no cell {w!r}")
        return [w for w in ws if w in cell_names]

    def common(metric, kind, what):
        if not _keys(metric, kind, what, faults, optional=("workloads",)):
            return False
        _name(metric["name"], what, faults)
        if not (isinstance(metric["unit"], str)
                and UNIT.match(metric["unit"])):
            faults.append(f"{what}: unit {metric['unit']!r} must be 1 to 16 "
                          f"characters from letters, digits, '_', '/', '%', "
                          f"'.' and '-'")
        if metric["better"] not in ("lower", "higher"):
            faults.append(f"{what}: better must be lower or higher")
        if metric["source"] not in SOURCES:
            faults.append(f"{what}: source must be one of {SOURCES}")
        return True

    e2e = manifest["end_to_end"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        faults.append("end_to_end: 1 to 16 metrics")
        e2e = []
    metric_names, e2e_cells = [], {}
    for m in e2e:
        what = f"end_to_end metric " \
               f"{m.get('name') if isinstance(m, dict) else m!r}"
        if not common(m, "end_to_end", what):
            continue
        metric_names.append(m["name"])
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"{what}: an end-to-end metric is taken from "
                          f"host_clock or device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.1):
            faults.append(f"{what}: bound must be from 0.01 to 0.1")
        e2e_cells[m["name"]] = listed(m, what)
    if "setup_s" not in e2e_cells:
        faults.append("end_to_end: one metric must be setup_s")
    elif set(e2e_cells["setup_s"]) != set(cell_names):
        faults.append("setup_s must be reported by every cell")

    per = manifest["per_layer"]
    if not (isinstance(per, list) and 1 <= len(per) <= 128):
        faults.append("per_layer: 1 to 128 metrics")
        per = []
    per_cells = {}
    for m in per:
        what = f"per_layer metric " \
               f"{m.get('name') if isinstance(m, dict) else m!r}"
        if not common(m, "per_layer", what):
            continue
        metric_names.append(m["name"])
        # the driver holds a layer to a name's characters: no space
        _name(m["layer"], f"{what} layer", faults)
        cells_of = per_cells[m["name"]] = listed(m, what)
        if m["moves"] not in e2e_cells:
            faults.append(f"{what}: moves {m['moves']!r}, which is no "
                          f"end-to-end metric")
        else:
            for w in cells_of:
                if w not in e2e_cells[m["moves"]]:
                    faults.append(f"{what}: cell {w} does not report "
                                  f"{m['moves']}, which this metric moves")
        if root and paths and not any(
                os.path.isfile(os.path.join(root, p, "metrics",
                                            str(m["name"]) + ".json"))
                for p in paths):
            faults.append(f"{what}: no reader file metrics/{m['name']}.json "
                          f"under paths")
        if m["unit"] == "%" and m["better"] != "higher" and (
                m["name"].endswith("_roofline") or "mfu" in m["name"]):
            faults.append(f"{what}: a roofline or MFU share is better higher")
    _unique(metric_names, "metric", faults)

    for w in cell_names:
        others = [n for n, ws in e2e_cells.items()
                  if w in ws and n != "setup_s"]
        if not others:
            faults.append(f"cell {w}: reports no end-to-end metric besides "
                          f"setup_s")
        if not any(w in ws for ws in per_cells.values()):
            faults.append(f"cell {w}: reports no per-layer metric")
    return faults


def _unique(names, what, faults):
    seen = set()
    for n in names:
        if n in seen:
            faults.append(f"two {what}s are named {n!r}")
        seen.add(n)


def load(path):
    with open(path) as f:
        return json.load(f)


def main(argv):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = argv[1] if len(argv) > 1 else os.path.join(here, "BENCHMARK.json")
    faults = check(load(path), os.path.dirname(os.path.abspath(path)))
    for f in faults:
        print(f"FAULT {f}")
    print(f"{path}: {'%d faults' % len(faults) if faults else 'ok'}")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
