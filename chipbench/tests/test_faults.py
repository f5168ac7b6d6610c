"""Each fault a cell can have, planted under the timed path, turns
``correct`` false; and each cell's control (its reference priced in the
precision below the configuration's) reads above its limit."""
import numpy as np

from conftest import TINY_MODEL, TINY_PRICE, TINY_SERVE, dry_run

PRICE = "price.miniapps.lhs256k"
SERVE = "serve.qwen2.5-3b.chat"


def _failed(line, name):
    chk = line["checks"][name]
    return not line["correct"] and chk["value"] > chk["limit"]


# ------------------------------------------------------------------ price
def test_price_answer_altered(monkeypatch):
    from repro.core import execplan
    orig = execplan._BACKENDS["pallas"]

    def altered(cb, view, plan):
        out = dict(orig(cb, view, plan))
        m = np.array(out["t_access_cxl_ns"], np.float64, copy=True)
        m[..., 0] *= 1.001                   # one call-site, every scenario
        out["t_access_cxl_ns"] = m
        return out
    monkeypatch.setitem(execplan._BACKENDS, "pallas", altered)
    line, _ = dry_run(PRICE, TINY_PRICE)
    assert _failed(line, "price_rel_err")


def test_price_stale_answer():
    """A sweep that returns the state of an earlier one."""
    def patch(drv):
        first = drv._sweep(drv.designs[0])
        drv._sweep = lambda design: first
    line, _ = dry_run(PRICE, dict(TINY_PRICE, check_sweeps=8), patch=patch)
    assert _failed(line, "price_rel_err")


def test_price_control_reads_above_limit():
    from chipbench.reference.pricing import bf16_round
    line, drv = dry_run(PRICE, TINY_PRICE)
    ctl = drv.compare(rnd=bf16_round)
    lim = drv.tr["limits"]
    assert ctl["components"] > lim["price_rel_err"]
    assert ctl["speedup"] > lim["speedup_rel_err"]


# ------------------------------------------------------------------ serve
def _serve_patch(fn):
    def patch(drv):
        fn(drv.engine)
    return patch


def test_serve_token_altered():
    def alter(e):
        sample = e._sample

        def bad(logits, key):
            tok = sample(logits, key)
            return tok.at[0].set((tok[0] + 1) % logits.shape[-1])
        e._sample = bad
    line, _ = dry_run(SERVE, TINY_SERVE, seconds=3.0,
                      patch=_serve_patch(alter))
    assert _failed(line, "served_gap_std")


def test_serve_decode_state_unchanged():
    """The decode step returns the cache it was given."""
    def freeze(e):
        step = e._decode_paged

        def frozen(params, pools, dense, tables, tokens, pos):
            keep = [None if p is None else {k: v.copy() for k, v in
                                            p.items()} for p in pools]
            logits, _, new_dense = step(params, pools, dense, tables,
                                        tokens, pos)
            return logits, keep, new_dense
        e._decode_paged = frozen
    line, _ = dry_run(SERVE, TINY_SERVE, seconds=3.0,
                      patch=_serve_patch(freeze))
    assert _failed(line, "served_gap_std")


def test_serve_half_the_batch():
    """The decode step computes the first half of the slots and gives the
    rest the first half's logits."""
    def halve(e):
        step = e._decode_paged

        def half(params, pools, dense, tables, tokens, pos):
            logits, new, new_dense = step(params, pools, dense, tables,
                                          tokens, pos)
            h = logits.shape[0] // 2
            return logits.at[h:].set(logits[:h]), new, new_dense
        e._decode_paged = half
    busy = dict(TINY_SERVE, rate=8.0, check_requests=24)
    line, _ = dry_run(SERVE, busy, seconds=3.0, patch=_serve_patch(halve))
    assert _failed(line, "served_gap_std")


#: a bfloat16 model small enough for the CPU, with outputs long enough that
#: about 300 served tokens are compared
CONTROL_SIZE = dict(
    TINY_SERVE,
    config={"model": dict(TINY_MODEL, hidden_size=256,
                          intermediate_size=512, num_hidden_layers=4,
                          vocab_size=4096, torch_dtype="bfloat16"),
            "engine": {"n_slots": 4, "max_len": 160, "block_size": 16}},
    output={"kind": "lognormal", "median": 32, "sigma": 0.5, "lo": 8,
            "hi": 64},
    check_requests=12)


def test_serve_control():
    """At this size (seed 1) the program in bfloat16 reads 0.02 std and
    the float8 control 0.73: the control comes out not correct against
    the cell's limit, set from readings at the cell's own size on the
    chip (0.055 and 5.06, PERF.md)."""
    line, drv = dry_run(SERVE, CONTROL_SIZE, seconds=3.0, seed=1)
    limit = drv.tr["limits"]["served_gap_std"]
    assert line["correct"]
    assert line["checks"]["served_gap_std"]["value"] < limit / 3
    assert drv.reference_gaps(drv.sample(), low=True)["gap_std"] > limit
