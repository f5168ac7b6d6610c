"""Model FLOP/s utilization of serving the Jamba hybrid (%): the forward
operations (``costs/hybrid_lm.py``) of every prompt and output token of
the requests due in the window, over the time from the window's start to
the end of the drain, over the chip's peak."""
from chipbench.costs import hybrid_lm as costs


def read(run):
    c = run.counters
    if "prompt_tokens" not in c or c["drain_end"] <= c["t0"]:
        return None
    m = run.cell.config
    ops = costs.forward_flops(m, c["ctx_sum"],
                              c["prompt_tokens"] + c["output_tokens"])
    return 100.0 * ops / (c["drain_end"] - c["t0"]) \
        / run.peaks["bf16_flops_per_s"]
