"""Engine tick (serve/engine.py): the host's time per tick in the traced
stretch that is not spent blocked on the chip: each ``tick`` span less the
``wait`` spans inside it, mean over ticks (ms). Read inside the program,
from its process-wide tracer (``repro.obs.trace.PROCESS_TRACER``), which
records only while the profiler runs; a program without it reads None."""


def read(run):
    try:
        from repro.obs.trace import PROCESS_TRACER as tracer
    except ImportError:
        return None
    if run.traced is None:
        return None
    ticks = tracer.spans("tick", *run.traced)
    if not ticks:
        return None
    host = sum(t.end - t.start
               - sum(w.end - w.start for w in tracer.spans("wait", t.start, t.end))
               for t in ticks)
    return 1e3 * host / len(ticks)
