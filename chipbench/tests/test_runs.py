"""Whole runs on the CPU: the refusals, a dry run of each driver at tiny
size past the look for a chip, and a cell added from files alone."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT, TINY_PRICE, TINY_SERVE, dry_run

RUN = ["chipbench/run.py", "--workload", "price.miniapps.lhs256k",
       "--seed", "3000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *RUN], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_price_dry_run():
    line, _ = dry_run("price.miniapps.lhs256k", TINY_PRICE)
    chk = line["checks"]
    assert chk["price_rel_err"]["value"] < chk["price_rel_err"]["limit"]
    assert chk["speedup_rel_err"]["value"] < chk["speedup_rel_err"]["limit"]
    # on the CPU the kernel runs in the interpreter: not the timed plan
    assert chk["plan_not_compiled_kernel"]["value"] == 1.0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"scenarios_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert list(line)[-1] == "checks"


def test_serve_dry_run():
    line, drv = dry_run("serve.qwen2.5-3b.chat", TINY_SERVE, seconds=3.0)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert line["attempted"] == 9 and line["failed"] == 0
    # latency runs from when a request was due, not from when it was sent
    for i, ts in drv.times.items():
        assert ts[0] >= drv.due[i]


def test_cell_added_from_files_alone(tmp_path):
    """A new configuration, traffic mix and per-layer metric are new files
    and new BENCHMARK.json entries; no file that exists is edited."""
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((ROOT / "chipbench/configs/miniapps-paper.json")
                     .read_text())
    cfg.update(name="stencil-only", bundles=[{"app": "stencil",
                                              "sizes": [32]}])
    (bench / "configs" / "stencil-only.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "chipbench/traffic/lhs256k.json")
                         .read_text())
    traffic.update(scenarios=16, sets=1, check_rows=8)
    (bench / "traffic" / "lhs16.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "calls_priced.price.py").write_text(
        "def read(run):\n    return float(run.counters['n_calls'])\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "stencil-only", "source": "x",
                            "file": "chipbench/configs/stencil-only.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "price.stencil.lhs16",
                              "config": "stencil-only", "traffic": "lhs16",
                              "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "scenarios_per_s":
            m["workloads"].append("price.stencil.lhs16")
    spec["per_layer"].append({
        "name": "calls_priced.price", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "sweep execution",
        "moves": "scenarios_per_s", "workloads": ["price.stencil.lhs16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    line, drv = dry_run("price.stencil.lhs16", spec_path=tmp_path /
                        "BENCHMARK.json", seconds=1.0)
    assert line["checks"]["price_rel_err"]["value"] < 1e-9
    assert drv.ctx.counters["n_calls"] == 4
    assert [m["name"] for m in drv.ctx.cell.per_layer] == \
        ["calls_priced.price"]
    from chipbench import harness
    mod = harness.load_module(bench / "metrics" / "calls_priced.price.py",
                              "calls_priced")
    assert mod.read(harness.Run(drv.ctx.cell, drv.ctx.counters, None,
                                {})) == 4.0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_serve_early_retirement_is_failed():
    """A request the engine retires before its ``max_new`` tokens is failed
    (infinitely late), not timed as if it had finished."""
    def early(drv):
        e = drv.engine
        emit = e._emit

        def short(slot, tok):
            emit(slot, tok)
            if e._slot_req[slot] is not None and e._emitted[slot] >= 3:
                e._retire(slot)
        e._emit = short
    line, drv = dry_run("serve.qwen2.5-3b.chat", TINY_SERVE, seconds=3.0,
                        patch=early)
    cut = sum(r.max_new > 3 for r in drv.reqs)
    assert cut > 0 and line["failed"] == cut
    assert line["metrics"]["ttft_p95_ms"]["value"] == float("inf")
