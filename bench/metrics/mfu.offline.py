"""Whole step's share of the chip's peak: model operations of the prompt
and output tokens processed in the traced window (each prompt once, its
logits at the last position; each decoded token at its own context; the
block's counts), over the window times the bf16 peak."""


def read(ctx):
    m, block = ctx.model, ctx.block
    ops = sum(block.prefill_flops(m, len(s.prompt)) for s in ctx.served)
    ops += sum(block.decode_token_flops(m, c) for c in ctx.decode_ctxs())
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * ops / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
