"""``BENCHMARK.json``: loaded, checked against the benchmark's rules, and
looked up by cell name.  A bad manifest is refused before any run."""
from __future__ import annotations

import json
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(ValueError):
    pass


def _name(v, what):
    if not isinstance(v, str) or not NAME.fullmatch(v):
        raise ManifestError(f"bad {what} {v!r}")


def _metric(m, e2e):
    _name(m["name"], "metric name")
    if not isinstance(m["unit"], str) or not UNIT.fullmatch(m["unit"]):
        raise ManifestError(f"bad unit {m['unit']!r} of {m['name']}")
    if m["better"] not in ("lower", "higher"):
        raise ManifestError(f"bad 'better' of {m['name']}")
    if m["source"] not in SOURCES:
        raise ManifestError(f"bad source of {m['name']}")
    if e2e and m["source"] not in ("host_clock", "device_trace"):
        raise ManifestError(f"end-to-end {m['name']} from {m['source']}")


def check(man):
    """Raise ManifestError unless ``man`` keeps the benchmark's rules."""
    if set(man) != TOP:
        raise ManifestError(f"keys {sorted(man)} are not {sorted(TOP)}")
    configs = {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        _name(c["name"], "config name")
        for k in c["reduced"]:
            _name(k, "reduced key")
    cells = set()
    for w in man["workloads"]:
        _name(w["name"], "cell name")
        _name(w["traffic"], "traffic name")
        if w["config"] not in configs:
            raise ManifestError(f"{w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
        cells.add(w["name"])
    e2e = set()
    for m in man["end_to_end"]:
        _metric(m, True)
        e2e.add(m["name"])
    for m in man["per_layer"]:
        _metric(m, False)
        if m["moves"] not in e2e:
            raise ManifestError(f"{m['name']} moves unknown {m['moves']}")
    for m in man["end_to_end"] + man["per_layer"]:
        if not set(m.get("workloads", cells)) <= cells:
            raise ManifestError(f"{m['name']} lists an unknown cell")
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in man[part]]
    if len(names) != len(set(names)):
        raise ManifestError("a name is used twice")
    return man


def load(path):
    with open(path) as f:
        return check(json.load(f))


def cell(man, name):
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no cell {name!r} in BENCHMARK.json")
