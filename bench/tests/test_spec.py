"""BENCHMARK.json against the benchmark's rules of form, and every file it
names present under bench/.

    python -m pytest -q bench/tests/test_spec.py
"""

from __future__ import annotations

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "cell": {"name", "config", "traffic", "chips", "why"},
    "e2e": {"name", "unit", "better", "bound", "source"},
    "layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_and_names():
    assert set(SPEC) == KEYS["top"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = []
    for c in SPEC["configs"]:
        assert set(c) == KEYS["config"] and _text(c["source"]) and _text(c["why"])
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == KEYS["cell"] and _text(w["why"])
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == KEYS["e2e"]
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
        names.append(m["name"])
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == KEYS["layer"] and _text(m["layer"])
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        names.append(m["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    pairs = set()
    for w in SPEC["workloads"]:
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert (BENCH / "entries" / f"{cell['entry']}.py").is_file()
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["name"] == w["config"]
        assert sorted(cfg["reduced"]) == sorted(configs[w["config"]]["reduced"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(SPEC["workloads"])
    assert {c["name"] for c in SPEC["configs"]} == {w["config"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_enough():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in SPEC["workloads"]:
        own = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in own} and len(own) >= 2
        layer = [m for m in SPEC["per_layer"] if w["name"] in m.get("workloads", [])]
        assert layer
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_run_seconds_fit_a_full_check():
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200
