"""Sampling (serve/engine.py): the host's time per tick in the traced
stretch spent sampling tokens from the fetched logits: the ``sample`` spans
inside each ``tick`` span, summed, mean over ticks (ms). Read inside the
program, from its process-wide tracer (``repro.obs.trace.PROCESS_TRACER``),
which records only while the profiler runs; a program without it reads
None."""


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
    spent = sum(s.end - s.start for t in ticks
                for s in tracer.spans("sample", t.start, t.end))
    return 1e3 * spent / len(ticks)
