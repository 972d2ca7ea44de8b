"""The manifest loader accepts BENCHMARK.json and refuses a bad name or
unit, and every cell's files exist."""
from bench_paths import BENCH  # noqa: F401  (sets the import path)

import copy
import json

import pytest

import manifest

MAN = BENCH.parent / "BENCHMARK.json"


def test_the_manifest_loads_and_every_name_has_its_files():
    man = manifest.load(MAN)
    for w in man["workloads"]:
        assert (BENCH / "traffic" / f"{w['name']}.json").is_file()
        assert (BENCH / "configs" / f"{w['config']}.json").is_file()
    for m in man["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in man["configs"]:
        assert json.loads((BENCH.parent / c["file"]).read_text())[
            "name"] == c["name"]


@pytest.mark.parametrize("part,field,bad", [
    ("workloads", "name", "ycsb a"),
    ("workloads", "name", "ycsb/a"),
    ("end_to_end", "name", "p99,ms"),
    ("per_layer", "name", "x" * 65),
    ("end_to_end", "unit", "txn per s"),
    ("per_layer", "unit", "µs"),
    ("per_layer", "unit", ""),
    ("end_to_end", "better", "faster"),
    ("per_layer", "moves", "no_such_metric"),
    ("workloads", "chips", 2),
])
def test_a_bad_name_or_unit_is_refused(part, field, bad):
    man = json.loads(MAN.read_text())
    bad_man = copy.deepcopy(man)
    bad_man[part][0][field] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.check(bad_man)


def test_an_unknown_key_or_cell_is_refused():
    man = json.loads(MAN.read_text())
    extra = dict(man, notes="x")
    with pytest.raises(manifest.ManifestError):
        manifest.check(extra)
    with pytest.raises(manifest.ManifestError):
        manifest.cell(man, "no_such_cell")
