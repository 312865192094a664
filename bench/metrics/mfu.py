"""Whole step: model FLOPs of the tokens served in the traced window (each
real token's weight matmuls, attention at its own position, and the
unembedding of each row whose logits were computed) over the window's
seconds times the chip's bf16 peak (%)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.model_flops(run.traced_ticks()) / (
        run.trace.window_s * run.peak["bf16_flops"])
