"""Share of its roofline that the paged decode step reaches on the Jamba
hybrid (%).

Per call, the algorithm needs every weight once, the live keys and values
of the active slots in the two attention layers, and the active slots'
recurrent state read and written (``costs/hybrid_lm.py``); and two
operations per matrix parameter per active slot, the scan's, and
attention over the live positions.  The least time is the larger of
operations over the chip's peak and bytes over HBM bandwidth; the share
is that over the mean device time of the traced decode calls.  Live
positions and active slots are the means over the host's steps during
the trace."""
from chipbench.costs import hybrid_lm as costs

NAME = "_decode_slots_paged"


def read(run):
    evs = run.device_events(NAME)
    t0, t1 = run.counters.get("trace_t0"), run.counters.get("trace_t1")
    steps = [s for s in run.counters.get("steps", ())
             if t0 is not None and t0 <= s[0] and s[1] <= t1 and s[2] > 0]
    if not evs or not steps:
        return None
    m = run.cell.config
    active = sum(s[2] for s in steps) / len(steps)
    live = sum(s[3] for s in steps) / len(steps)
    nbytes = 2 * costs.all_params(m) + live * costs.kv_bytes_per_token(m) \
        + 2 * active * costs.state_bytes_per_slot(m)
    ops = costs.forward_flops(m, live, active)
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    per_call = sum(e.dur for e in evs) / len(evs) * 1e-9
    return 100.0 * least / per_call
