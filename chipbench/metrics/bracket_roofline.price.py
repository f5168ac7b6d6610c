"""Share of its roofline that the fused bracket kernel reaches (%).

The least time for one call is the larger of its operations over the
chip's peak rate and its bytes over the HBM bandwidth (``costs/bracket``);
the share is that, times the calls in the trace, over the kernel's device
time there.  One kernel call prices every scenario of one sweep."""
from chipbench.costs.bracket import bracket_bytes, bracket_ops

KERNEL = "bracket"


def read(run):
    if run.trace is None:
        return None
    evs = run.trace.device_events(KERNEL, line="XLA Ops")
    if not evs:
        return None
    c = run.counters
    n = (c["scenarios_per_sweep"], c["n_hit"], c["n_lfb"], c["n_miss"])
    least = max(bracket_ops(*n) / run.peaks["bf16_flops_per_s"],
                bracket_bytes(*n, c["n_calls"])
                / run.peaks["hbm_bytes_per_s"])
    calls = len(evs)
    dev_s = sum(e.dur for e in evs) * 1e-9
    return 100.0 * least * calls / dev_s
