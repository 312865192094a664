"""Model step (models/lm.decode_step): mean device time of one decode
program execution in the traced window (ms)."""


def read(run):
    if run.trace is None:
        return None
    prog = run.trace.programs.get("decode")
    if prog is None or not prog.count:
        return None
    return prog.device_s * 1e3 / prog.count
