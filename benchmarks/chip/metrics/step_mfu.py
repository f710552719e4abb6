"""Model FLOPs of a step (counts.step_flops: 6 x matmul parameters x
tokens, plus the sequence mixer) times steps per second of the traced
window, over the chip's peak bf16 FLOP/s, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    rate = ctx.window.steps_per_s()
    return 100.0 * ctx.step_flops * rate / ctx.peaks["bf16_flops_per_s"]
