"""Model step (models/lm.prefill_chunk): device time of the chunked-prefill
program in the traced window, per thousand prompt tokens it carried
(ms per 1000 tokens)."""


def read(run):
    if run.trace is None:
        return None
    prog = run.trace.programs.get("prefill")
    tokens = sum(n for t in run.traced_ticks() for _, n in t.prefill_tokens)
    if prog is None or not tokens:
        return None
    return prog.device_s * 1e3 / (tokens / 1e3)
