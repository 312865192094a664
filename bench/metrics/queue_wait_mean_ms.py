"""Scheduler (serve/scheduler.py): mean, over requests due in the window,
of the time from due to the tick that bound it to a lane (ms). A request
waits when no lane is free or the KV pool cannot reserve its blocks."""


def read(run):
    waits = [r.bind_s - r.due_s for r in run.attempted() if r.bind_s is not None]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
