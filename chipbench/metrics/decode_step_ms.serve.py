"""Device time per paged-decode call (ms): the mean length of the traced
executions of the engine's jitted decode step over all slots."""
NAME = "_decode_slots_paged"


def read(run):
    evs = run.device_events(NAME)
    return sum(e.dur for e in evs) / len(evs) * 1e-6 if evs else None
