"""Scheduler: lanes served per decode step, averaged over the window's
ticks that ran one (the engine's occupancy counter)."""


def read(run):
    lanes = [t.decode_lanes for t in run.window_ticks() if t.decode_lanes]
    return sum(lanes) / len(lanes) if lanes else None
