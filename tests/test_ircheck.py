"""IR-tier checker tests (``repro.analysis.ircheck``).

Seeded-bad entry specs must trip exactly their pass — dead donation,
f64 promotion, host callback, busted budget — while clean specs stay
silent; the collective audit is unit-tested on synthetic HLO (mesh
mismatch needs multi-device lowering, which CI covers under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``); and the CLI
honors the ``file:line rule message`` / nonzero-exit contract shared
with ``repro.lint``.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ircheck as irc

F32 = jnp.float32
BUILTIN_ENTRIES = {"serve.paged_decode", "serve.paged_prefill_chunk",
                   "sweep.price_grid_jax", "sweep.price_topk_chunk",
                   "train.step"}


def x8():
    return jax.ShapeDtypeStruct((8, 8), F32)


# ------------------------------------------------------------- registry

def test_builtin_entrypoints_registered():
    assert BUILTIN_ENTRIES <= set(irc.known_entrypoints())


def test_registry_rejects_duplicate_unless_overwrite():
    irc.register_entrypoint("tmp.dup", lambda: None)
    try:
        with pytest.raises(ValueError, match="already registered"):
            irc.register_entrypoint("tmp.dup", lambda: None)
        irc.register_entrypoint("tmp.dup", lambda: None, overwrite=True)
    finally:
        irc._ENTRYPOINTS.pop("tmp.dup", None)


def test_check_unknown_entrypoint_errors():
    with pytest.raises(ValueError, match="unknown entry point"):
        irc.check_entrypoints(["not.an.entry"])


# ----------------------------------------------------------- clean spec

def test_clean_entry_is_ok_with_metrics():
    spec = irc.EntrySpec("t.clean", lambda x: jnp.tanh(x @ x),
                         args=(x8(),))
    rep = irc.check_entry(spec)
    assert rep.status == "ok" and rep.findings == []
    assert rep.metrics["peak_live_bytes"] > 0
    assert "copy_transpose_bytes" in rep.metrics


# ------------------------------------------------------- donation pass

def test_dead_donation_is_a_finding():
    # scalar output cannot alias the donated (8,8) input
    spec = irc.EntrySpec("t.deaddon", lambda x: jnp.sum(x),
                         args=(x8(),), donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")       # jax's own donation warning
        rep = irc.check_entry(spec)
    assert rep.status == "findings"
    assert [f.rule for f in rep.findings] == ["donation-dead"]
    assert "[t.deaddon]" in rep.findings[0].message


def test_live_donation_is_clean():
    spec = irc.EntrySpec("t.livedon", lambda x: x + 1.0,
                         args=(x8(),), donate_argnums=(0,))
    rep = irc.check_entry(spec)
    assert "donation-dead" not in {f.rule for f in rep.findings}


# ------------------------------------------------------ promotion pass

def test_silent_f64_promotion_is_a_finding():
    spec = irc.EntrySpec("t.promo", lambda x: x * np.float64(1.5),
                         args=(x8(),))
    rep = irc.check_entry(spec)
    assert "f64-promotion" in {f.rule for f in rep.findings}


def test_x64_entry_exempt_from_promotion_pass():
    spec = irc.EntrySpec("t.promo64", lambda x: x * np.float64(1.5),
                         args=(x8(),), x64=True)
    rep = irc.check_entry(spec)
    assert "f64-promotion" not in {f.rule for f in rep.findings}


# ------------------------------------------------------- callback pass

def _printing(x):
    jax.debug.print("x sum {}", jnp.sum(x))
    return x + 1.0


def test_host_callback_is_a_finding_unless_allowed():
    rep = irc.check_entry(irc.EntrySpec("t.cb", _printing, args=(x8(),)))
    assert "host-callback" in {f.rule for f in rep.findings}

    allowed = irc.EntrySpec("t.cb.ok", _printing, args=(x8(),),
                            allow_effects=("ebug",))
    rep = irc.check_entry(allowed)
    assert "host-callback" not in {f.rule for f in rep.findings}


# ----------------------------------------------------- collective pass

SYNTH_AR = """\
ENTRY %main (p0: f32[16]) -> f32[16] {
  %p0 = f32[16]{0} parameter(0)
  ROOT %ar = f32[16]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""


def test_collective_matching_mesh_is_clean():
    assert irc.collective_findings(SYNTH_AR, {"x": 4}) == []
    # 4 = 2 x 2 is a valid product of axis sizes
    assert irc.collective_findings(SYNTH_AR, {"dp": 2, "tp": 2}) == []


def test_collective_mesh_mismatch_flagged():
    msgs = irc.collective_findings(SYNTH_AR, {"x": 3})
    assert len(msgs) == 1 and "not a product" in msgs[0]
    assert "x=3" in msgs[0]


def test_collective_without_registered_mesh_flagged():
    msgs = irc.collective_findings(SYNTH_AR, None)
    assert len(msgs) == 1 and "registered no mesh" in msgs[0]


def test_degenerate_single_member_collective_flagged():
    text = SYNTH_AR.replace("{{0,1,2,3}}", "{{0}}")
    msgs = irc.collective_findings(text, {"x": 4})
    assert len(msgs) == 1 and "degenerate" in msgs[0]


def test_no_collectives_no_findings():
    assert irc.collective_findings("ENTRY %main () -> f32[] {\n}\n",
                                   None) == []


# -------------------------------------------------------- jaxpr passes

def test_peak_live_bytes_counts_simultaneous_liveness():
    closed = jax.make_jaxpr(lambda x: jnp.tanh(x @ x))(
        jax.ShapeDtypeStruct((16, 16), F32))
    peak = irc.peak_live_bytes(closed)
    # x and x@x are live together: at least 2 KiB, and the whole
    # three-value program never exceeds 4 KiB
    assert 2 * 16 * 16 * 4 <= peak <= 4 * 16 * 16 * 4


def test_peak_live_bytes_while_body_carry_aliasing():
    # while outputs alias the carries: inside the body only carry (16 KiB)
    # + one temporary (16 KiB) are ever live together, so the estimate
    # must stay at ~2 tiles — before the aliasing refinement the loop's
    # outputs were counted on top of the body peak (~3 tiles).
    n = 64 * 64 * 4

    def f(x):
        return jax.lax.while_loop(lambda c: c[1] < 3,
                                  lambda c: (c[0] * 2.0 + 1.0, c[1] + 1),
                                  (x, 0))

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((64, 64), F32))
    peak = irc.peak_live_bytes(closed)
    assert n <= peak <= 2 * n + 64, peak


def test_peak_live_bytes_scan_carry_aliasing():
    # scan's first num_carry outputs alias the carry; the stacked ys are
    # real allocations and must still be counted.
    n = 64 * 64 * 4

    def f(x):
        def body(c, _):
            c = c * 2.0 + 1.0
            return c, jnp.sum(c)
        return jax.lax.scan(body, x, None, length=4)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((64, 64), F32))
    peak = irc.peak_live_bytes(closed)
    assert n <= peak <= 2 * n + 256, peak


def test_aliased_out_bytes_zero_for_plain_eqns():
    closed = jax.make_jaxpr(lambda x: x @ x)(
        jax.ShapeDtypeStruct((16, 16), F32))
    j = closed.jaxpr
    assert all(irc._aliased_out_bytes(eqn) == 0 for eqn in j.eqns)


def test_f64_promotions_unit():
    from repro.compat import enable_x64
    with enable_x64():
        closed = jax.make_jaxpr(lambda x: x * np.float64(1.5))(
            jax.ShapeDtypeStruct((4,), F32))
    promos = irc.f64_promotions(closed)
    assert promos and all(n >= 1 for n in promos.values())


# ----------------------------------------------------- baseline budgets

def test_busted_budget_is_a_finding():
    spec = irc.EntrySpec("t.budget", lambda x: jnp.tanh(x @ x),
                         args=(x8(),))
    rep = irc.check_entry(spec, baseline_entry={"peak_live_bytes": 16,
                                                "copy_transpose_bytes": 0})
    assert "peak-live-bytes" in {f.rule for f in rep.findings}


def test_in_budget_is_clean_and_slack_absorbs_drift():
    spec = irc.EntrySpec("t.budget.ok", lambda x: jnp.tanh(x @ x),
                         args=(x8(),))
    rep = irc.check_entry(spec)
    base = dict(rep.metrics)
    assert irc.check_entry(spec, baseline_entry=base).findings == []
    # 20% growth sits inside the default 25% slack
    shrunk = {k: max(1, int(v / 1.2)) for k, v in base.items()}
    assert irc.check_entry(spec, baseline_entry=shrunk).findings == []


def test_missing_budget_metric_is_a_finding():
    spec = irc.EntrySpec("t.nobudget", lambda x: x + 1.0, args=(x8(),))
    rep = irc.check_entry(spec, baseline_entry={})
    assert {f.rule for f in rep.findings} == {"baseline-missing"}


def test_write_and_load_baseline_roundtrip_merges(tmp_path):
    p = tmp_path / "base.json"
    assert irc.load_baseline(p) is None
    rep_a = irc.EntryReport("a", "ok", metrics={"peak_live_bytes": 10,
                                                "copy_transpose_bytes": 2})
    irc.write_baseline(p, [rep_a], slack=0.25)
    rep_b = irc.EntryReport("b", "ok", metrics={"peak_live_bytes": 7,
                                                "copy_transpose_bytes": 0})
    out = irc.write_baseline(p, [rep_b], slack=0.25)
    assert set(out["entries"]) == {"a", "b"}      # merge keeps 'a'
    assert irc.load_baseline(p) == out
    assert out["slack"] == 0.25


def test_committed_baseline_covers_all_builtins():
    base = irc.load_baseline(irc.REPO_ROOT / irc.BASELINE_NAME)
    assert base is not None, "IRCHECK_baseline.json must be committed"
    assert BUILTIN_ENTRIES <= set(base["entries"])
    for entry in base["entries"].values():
        assert set(entry) == {"copy_transpose_bytes", "peak_live_bytes"}


# ------------------------------------------------- min-devices gating

def test_sharded_entry_skips_below_min_devices():
    if jax.device_count() >= 4:
        pytest.skip("multi-device process: the entry actually runs")
    reports = irc.check_entrypoints(["sweep.price_topk_chunk"])
    assert len(reports) == 1
    assert reports[0].status == "skipped"
    assert "XLA_FLAGS" in reports[0].note


# ---------------------------------------------------------------- CLI

def test_cli_list_prints_entries(capsys):
    assert irc.main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in BUILTIN_ENTRIES:
        assert name in out


def test_cli_unknown_entry_is_usage_error(capsys):
    assert irc.main(["--entry", "not.an.entry"]) == 2
    assert "unknown entry point" in capsys.readouterr().err


def test_cli_seeded_bad_entry_exits_nonzero_with_contract(capsys):
    irc.register_entrypoint(
        "tmpbad.donation",
        lambda: irc.EntrySpec("tmpbad.donation", lambda x: jnp.sum(x),
                              args=(x8(),), donate_argnums=(0,)))
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            code = irc.main(["--entry", "tmpbad.donation"])
        assert code == 1
        out = capsys.readouterr().out.strip().splitlines()
        # the repro.lint contract: path:line rule message
        assert out and out[0].split()[1] == "donation-dead"
        head = out[0].split()[0]
        path, _, line = head.rpartition(":")
        assert path.endswith(".py") and line.isdigit()
    finally:
        irc._ENTRYPOINTS.pop("tmpbad.donation", None)


def test_cli_json_format_end_to_end(capsys):
    assert irc.main(["--entry", "sweep.price_grid_jax",
                     "--format", "json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["tool"] == "repro.analysis.ircheck"
    assert payload["n_findings"] == 0
    (entry,) = payload["entries"]
    assert entry["name"] == "sweep.price_grid_jax"
    assert entry["status"] == "ok"
    assert entry["metrics"]["peak_live_bytes"] > 0
