"""check_manifest.py against the committed manifest, and against each fault
the contract names, made in the toy manifest (four cells, every metric)."""
import copy
import json
import os

import pytest

import check_manifest
import toy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    check_manifest.__file__)))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toymanifest"))
    toy.build(root)
    return root


@pytest.fixture()
def manifest(toy_root):
    with open(os.path.join(toy_root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_and_toy_manifests_are_valid(manifest, toy_root):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert check_manifest.check(json.load(f), REPO) == []
    assert check_manifest.main(["check_manifest.py"]) == 0
    assert check_manifest.check(manifest, toy_root) == []


@pytest.mark.parametrize("which", ["committed", "toy"])
def test_every_name_unit_and_layer_keeps_to_its_characters(manifest, which):
    if which == "committed":
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in manifest[k]]
    names += [m["layer"] for m in manifest["per_layer"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    assert all(check_manifest.NAME.match(n) for n in names), names
    assert all(check_manifest.UNIT.match(m["unit"])
               for k in ("end_to_end", "per_layer") for m in manifest[k])


def _break(manifest, edit, root):
    m = copy.deepcopy(manifest)
    edit(m)
    return check_manifest.check(m, root)


CASES = {
    # PR 22's fault: a layer named in plain words
    "layer_with_space": (lambda m: m["per_layer"][0].update(
        layer="boosting loop"), "layer"),
    "metric_name_with_slash": (lambda m: m["per_layer"][0].update(
        name="fit/bins"), "fit/bins"),
    "unit_with_star": (lambda m: m["end_to_end"][0].update(
        unit="rows*iters/s"), "unit"),
    "unit_too_long": (lambda m: m["end_to_end"][0].update(
        unit="x" * 17), "unit"),
    "metric_lists_unknown_cell": (lambda m: m["per_layer"][0].update(
        workloads=["nowhere"]), "no cell 'nowhere'"),
    "moves_metric_cell_lacks": (lambda m: m["per_layer"][0].update(
        moves="lm_tokens_per_s"), "does not report lm_tokens_per_s"),
    "moves_unknown_metric": (lambda m: m["per_layer"][0].update(
        moves="nothing"), "no end-to-end metric"),
    "two_four_chip_cells_of_four": (lambda m: m["workloads"][0].update(
        chips=4), "4 chips"),
    "config_without_cell": (lambda m: m["workloads"].pop(2),
                            "no cell uses it"),
    "cell_names_missing_traffic_file": (lambda m: m["workloads"][0].update(
        traffic="no-such-mix"), "no data file"),
    "config_file_missing": (lambda m: m["configs"][0].update(
        file="benchmark/configs/none.json"), "cannot read"),
    "config_file_outside_paths": (lambda m: m["configs"][0].update(
        file="bench.py"), "under paths"),
    "extra_key_on_metric": (lambda m: m["per_layer"][0].update(
        why="because"), "not allowed"),
    "bound_too_wide": (lambda m: m["end_to_end"][0].update(bound=0.2),
                       "bound"),
    "no_setup_s": (lambda m: m["end_to_end"].pop(), "setup_s"),
    "run_seconds_too_long": (lambda m: m.update(run_seconds=52),
                             "run_seconds"),
    "reduced_names_a_width": (lambda m: m["configs"][2].update(
        reduced=["n_embd"]), "width"),
    "pair_twice": (lambda m: m["workloads"].append(dict(
        m["workloads"][0], name="again")), "appears twice"),
    "command_leaves_repo": (lambda m: m.update(
        command=["python3", "../x.py"]), "leaves the repo"),
    "metric_without_reader_file": (lambda m: m["per_layer"][0].update(
        name="unread_s"), "no reader file"),
    "two_metrics_one_name": (lambda m: m["per_layer"][1].update(
        name=m["per_layer"][0]["name"]), "two metrics"),
    "top_level_key": (lambda m: m.update(notes="x"), "top level"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fault_is_found(manifest, toy_root, case):
    edit, needle = CASES[case]
    faults = _break(manifest, edit, toy_root)
    assert any(needle in f for f in faults), faults
