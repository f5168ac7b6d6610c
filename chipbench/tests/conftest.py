"""Run on the CPU: ``pytest chipbench/tests`` from the checkout root."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

#: a qwen2-shaped model small enough for the CPU, in float32
TINY_MODEL = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "torch_dtype": "float32"}
TINY_SERVE = {
    "config": {"model": TINY_MODEL,
               "engine": {"n_slots": 4, "max_len": 96, "block_size": 16}},
    "rate": 3.0,
    "prompt": {"kind": "lognormal", "median": 20, "sigma": 0.8, "lo": 4,
               "hi": 64},
    "output": {"kind": "lognormal", "median": 8, "sigma": 0.7, "lo": 2,
               "hi": 32}}
TINY_PRICE = {
    "config": {"bundles": [{"app": "stencil", "sizes": [32, 128]},
                           {"app": "hpcg", "sizes": [16]}]},
    "scenarios": 64, "sets": 2, "check_rows": 32}


def dry_run(workload, overrides=None, seconds=2.0, seed=2**31 + 77,
            spec_path=None, patch=None):
    """One run of a cell on the CPU, past the look for a chip.  Returns
    ``(result line, driver)``; ``patch(driver)`` runs after set-up."""
    import copy

    import jax

    from chipbench import harness
    from chipbench import run as runner
    cell = harness.load_cell(workload, spec_path)
    over = copy.deepcopy(overrides or {})
    cell.config.update(over.pop("config", {}))
    cell.traffic.update(over)
    ctx = harness.Context(cell=cell, seed=seed, jax=jax,
                          devices=jax.devices()[:1])
    mod = harness.load_module(
        cell.bench / "drivers" / f"{cell.config['driver']}.py",
        f"cb_test_driver_{workload}")
    driver = mod.Driver(ctx)
    if patch is not None:
        setup = driver.setup

        def patched():
            setup()
            patch(driver)
        driver.setup = patched
    line = runner.measure(ctx, driver, seconds, False, say=lambda *a: None)
    return line, driver


@pytest.fixture
def tiny():
    return {"serve": TINY_SERVE, "price": TINY_PRICE}
