"""``BENCHMARK.json`` keeps to its format, and every name in it resolves
to a file of the benchmark."""
import json
import re

from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source",
                   "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"}}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_shape_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (SPEC["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    for group, keys in KEYS.items():
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        for e in SPEC[group]:
            assert set(e) <= keys and set(e) >= keys - {"workloads"}
            assert NAME.match(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert _text(e[k]), (e["name"], k)
            if "unit" in e:
                assert UNIT.match(e["unit"])
                assert e["better"] in ("lower", "higher")


def test_files_and_references():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("chipbench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").exists()
        body = json.loads((ROOT / cfgs[w["config"]]["file"]).read_text())
        assert (ROOT / "chipbench/drivers" / f"{body['driver']}.py").exists()
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        shown = [m for m in e2e.values() if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in shown] and len(shown) >= 2
        assert any(w in m.get("workloads", cells)
                   for m in SPEC["per_layer"])
    assert len(json.dumps(SPEC)) < 64 * 1024
