"""Share of its roofline that the chunk-prefill step reaches on the Jamba
hybrid (%).

Each prompt admitted in a step that lies wholly inside the trace is cut
into the engine's chunks; a chunk's least time is that of its REAL prompt
tokens (``costs/hybrid_lm.chunk_least_s``: the larger of operations over
the chip's peak and bytes — every weight once, the keys and values
attended, one slot's state read and written — over HBM bandwidth).  The
share is the mean least time of those chunks over the mean device time
of the traced chunk-step calls."""
from chipbench.costs import hybrid_lm as costs

NAME = "_prefill_chunk_step"


def read(run):
    evs = run.device_events(NAME)
    t0, t1 = run.counters.get("trace_t0"), run.counters.get("trace_t1")
    prompts = [a[2] for a in run.counters.get("admissions", ())
               if t0 is not None and t0 <= a[0] and a[1] <= t1]
    if not evs or not prompts:
        return None
    m = run.cell.config
    bs = run.cell.config["engine"]["block_size"]
    least = [costs.chunk_least_s(m, s, min(bs, n - s), run.peaks)
             for n in prompts for s in range(0, n, bs)]
    per_call = sum(e.dur for e in evs) / len(evs) * 1e-9
    return 100.0 * sum(least) / len(least) / per_call
