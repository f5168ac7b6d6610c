"""Host time per pricing sweep (ms): each traced ``cb.sweep`` span's length
minus the device's busy time inside it, averaged over the sweeps that lie
wholly in the trace."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("cb.sweep")
    if not spans:
        return None
    host = [s.dur - run.trace.busy_ns(s.start, s.end) for s in spans]
    return sum(host) / len(host) * 1e-6
