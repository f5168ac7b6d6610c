"""Profiler spans of the pricing engine and the serving engine.

One CPU profiler session records tiny ``price()`` calls (a bundle list
twice, one compiled bundle twice, one streaming sweep) and a few steps of
the paged engine; the test reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData`` and checks span names, nesting and stats.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import (ModelParams, ParamGrid, adaptive_sample,
                        compile_bundle, price)
from repro.core.spans import span
from repro.models.factory import make_model
from repro.serve import PagedContinuousEngine
from test_sweep_backends import small_bundle

CFG = ARCHS["qwen2.5-3b"].reduced()
BS = 4


class Span:
    def __init__(self, ev):
        self.name = ev.name
        self.start = float(ev.start_ns)
        self.end = self.start + float(ev.duration_ns)
        self.stats = dict(ev.stats)

    def inside(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


def _read_spans(out_dir: Path) -> list:
    from jax.profiler import ProfileData
    path = sorted(out_dir.glob("**/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    spans = [Span(e) for plane in pd.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return sorted(spans, key=lambda s: s.start)


def _engine():
    model = make_model(CFG, moe_impl="dense")
    return PagedContinuousEngine(model=model,
                                 params=model.init(jax.random.PRNGKey(0)),
                                 n_slots=2, max_len=24, block_size=BS)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Run every traced path once inside one profiler session; returns the
    spans by section plus what the engine's state was at each decode."""
    grid = ParamGrid.product(ModelParams.multinode(),
                             cxl_lat_ns=[300.0, 500.0],
                             cxl_atomic_lat_ns=[350.0, 550.0, 750.0])
    bundles = [small_bundle(seed=3), small_bundle(seed=4, n_sites=2)]
    cb = compile_bundle(small_bundle(seed=5))
    design = adaptive_sample(ModelParams.multinode(), 40, seed=2,
                             cxl_lat_ns=(250.0, 700.0))
    eng = _engine()
    eng.run([(np.arange(1, 4), 2)])               # compile outside the trace
    seen, reads = [], []
    decode = eng._decode_active

    def watched():
        active = [s for s in range(eng.n_slots)
                  if eng._slot_req[s] is not None]
        seen.append((len(active), int(sum(eng._pos[s] + 1 for s in active))))
        reads.append(sum(int(eng._pos[s]) // BS + 1 for s in active))
        return decode()

    eng._decode_active = watched
    prompts = [np.arange(1, 7), np.arange(2, 11), np.arange(3, 6)]

    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    marks, results = {}, {}
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        for section, run in (
                ("list", lambda: price(bundles, grid, plan="jax")),
                ("single", lambda: price(cb, grid, plan="jax")),
                ("stream", lambda: price(
                    cb, design, plan="distributed:topk=4,chunk=16")),
                ("chunked", lambda: price(bundles, grid,
                                          plan="jax:chunk=4"))):
            for _ in range(2 if section in ("list", "single") else 1):
                with jax.profiler.TraceAnnotation("test." + section):
                    results[section] = run()
        with jax.profiler.TraceAnnotation("test.serve"):
            rids = [eng.submit(p, 3) for p in prompts]
            for _ in range(4):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(sorted(out.glob("**/*.xplane.pb"))[-1]))
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("test."):
                    marks.setdefault(e.name[5:], []).append(Span(e))
    spans = _read_spans(out)
    by = {k: [s for s in spans if any(s.inside(m) for m in v)]
          for k, v in marks.items()}
    return {"by": by, "seen": seen, "reads": reads, "engine": eng,
            "rids": rids,
            "grid": grid, "results": results}


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_price_list_spans_nest_and_miss_every_call(recorded):
    spans, grid = recorded["by"]["list"], recorded["grid"]
    tops = _named(spans, "repro.price")
    assert len(tops) == 2
    for top in tops:
        assert top.stats == {"backend": "jax", "bundles": 2,
                             "scenarios": len(grid)}
        inner = [s for s in spans if s is not top and s.inside(top)]
        assert [s.name for s in inner] == [
            "repro.price.pack", "repro.price.run", "repro.price.fetch",
            "repro.price.split"]
        assert inner[0].stats == {"calls": 5}
    # each call packs a new super-bundle, so its jit is built again
    assert [s.stats["jit_miss"] for s in _named(spans, "repro.price.run")] \
        == [1, 1]


def test_fetch_counts_the_float64_written_once(recorded):
    """One call's assembly writes each of the four float64 matrices of
    every bundle once: ``4 * S * C * 8`` bytes, C the calls of all."""
    spans, S = recorded["by"]["list"], len(recorded["grid"])
    fetches = _named(spans, "repro.price.fetch")
    assert [f.stats["host_mb"] for f in fetches] == [4 * S * 5 * 8 / 1e6] * 2


def test_chunked_fetch_writes_rows_into_c_ordered_matrices(recorded):
    """A chunking plan writes each chunk's rows into C-ordered matrices:
    one fetch per chunk, counting that chunk's float64 only."""
    spans = recorded["by"]["chunked"]
    S = len(recorded["grid"])
    assert [f.stats["host_mb"] for f in _named(spans, "repro.price.fetch")] \
        == [4 * 4 * 5 * 8 / 1e6, 4 * (S - 4) * 5 * 8 / 1e6]
    for r in recorded["results"]["chunked"]:
        for f in ("t_transfer_mpi_ns", "t_transfer_cxl_ns",
                  "t_access_mpi_ns", "t_access_cxl_ns"):
            m = getattr(r, f)
            assert m.flags.writeable and m.flags.c_contiguous
            assert m.shape == (S, r.compiled.n_calls)


def test_price_single_bundle_hits_its_jit_cache(recorded):
    spans = recorded["by"]["single"]
    assert [s.stats["bundles"] for s in _named(spans, "repro.price")] \
        == [1, 1]
    assert not _named(spans, "repro.price.pack")
    assert [s.stats["jit_miss"] for s in _named(spans, "repro.price.run")] \
        == [1, 0]


def test_streaming_sweep_spans(recorded):
    spans = recorded["by"]["stream"]
    (top,) = _named(spans, "repro.price")
    assert top.stats["backend"] == "distributed"
    merges = _named(spans, "repro.price.merge")
    assert [s.stats["rows"] for s in merges] == [16, 16, 8]
    (exact,) = _named(spans, "repro.price.exact")
    assert exact.stats == {"rows": 4}
    runs = _named(spans, "repro.price.run")
    # three chunks share one shard geometry; the exact pass reuses the
    # bundle's jax executable from the single-bundle calls
    assert [s.stats["jit_miss"] for s in runs] == [1, 0, 0, 0]
    assert runs[-1].inside(exact)
    assert all(s.inside(top) for s in merges + runs)


def test_serve_step_spans_match_engine_state(recorded):
    spans, seen = recorded["by"]["serve"], recorded["seen"]
    steps = _named(spans, "repro.serve.step")
    assert len(steps) == 4
    assert [(s.stats["active"], s.stats["live"]) for s in steps] == seen
    # two slots: the third request is admitted when both others retire
    assert [s.stats["queued"] for s in steps] == [3, 1, 1, 0]
    assert [s.stats["admitted"] for s in steps] == [2, 0, 1, 0]
    # chunked prefill: ceil(prompt / block) chunks per admission
    assert [s.stats["chunks"] for s in steps] == [2 + 3, 0, 1, 0]
    admits = _named(spans, "repro.serve.admit")
    assert [(a.stats["prompt"], a.stats["chunks"]) for a in admits] == \
        [(6, 2), (9, 3), (3, 1)]
    assert all(a.stats["waited_us"] >= 0 for a in admits)
    for a in admits:
        assert any(a.inside(s) for s in steps)
    for name in ("repro.serve.decode", "repro.serve.sync",
                 "repro.serve.emit"):
        parts = _named(spans, name)
        assert len(parts) == 4
        assert all(any(p.inside(s) for s in steps) for p in parts)


def test_decode_span_counts_the_kv_blocks_read(recorded):
    """``kv_blocks_read``: Σ over the active slots of ``ceil((pos + 1) /
    block_size)``; ``kv_blocks_full``: ``n_slots * max_blocks``; the
    engine's ``ServeStats`` sum both over every decode it ran."""
    eng = recorded["engine"]
    decodes = _named(recorded["by"]["serve"], "repro.serve.decode")
    assert [d.stats["kv_blocks_read"] for d in decodes] == recorded["reads"]
    assert all(d.stats["kv_blocks_full"] == 2 * -(-24 // BS)
               for d in decodes)
    # the warm-up run before the trace: one decode of one slot at pos 3
    assert eng.stats.kv_blocks_read == 1 + sum(recorded["reads"])
    assert eng.stats.kv_blocks_full == \
        eng.stats.decode_steps * 2 * -(-24 // BS)


def test_queued_stamp_precedes_admission(recorded):
    eng, rids = recorded["engine"], recorded["rids"]
    for rid in rids:
        t = eng.req_times[rid]
        assert t["queued"] <= t["visible"] <= t["first"] <= t["done"]
    # the third request waited a whole request for a free slot
    admits = _named(recorded["by"]["serve"], "repro.serve.admit")
    assert admits[2].stats["waited_us"] > admits[0].stats["waited_us"]


def test_run_keeps_visible_at_arrival():
    eng = _engine()
    eng.run([(np.arange(1, 5), 2, 0), (np.arange(1, 5), 2, 3)])
    late = eng.req_times[1]
    assert late["queued"] < late["visible"] <= late["first"]


def test_span_is_a_null_context_without_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    with span("repro.price", backend="numpy") as s:
        assert s is None
