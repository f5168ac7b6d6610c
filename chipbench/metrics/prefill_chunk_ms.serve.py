"""Device time per chunk-prefill call (ms): the mean length of the traced
executions of the engine's jitted chunk step."""
NAME = "_prefill_chunk_step"


def read(run):
    evs = run.device_events(NAME)
    return sum(e.dur for e in evs) / len(evs) * 1e-6 if evs else None
