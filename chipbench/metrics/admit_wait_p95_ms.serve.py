"""95th percentile of the wait from a request's due time to the start of
the ``engine.step()`` call that admitted it, over the requests admitted
before the window closed (ms, harness clock)."""
from chipbench.harness import percentile


def read(run):
    waits = run.counters.get("admit_wait_s")
    return percentile(waits, 95) * 1e3 if waits else None
