"""Model FLOP utilisation of the collection step: the FLOPs that one
forward over every prompt and response token of the window's batches
requires (2 per matmul parameter per token, output head included, plus
causal attention), over the window's wall time times the chip's bf16 peak.
Reused and decoded tokens count the same work."""


def read(ctx):
    c = ctx.config
    work = 0.0
    for r in ctx.records:
        for p, L in zip(r.prompt_len, r.length):
            work += ctx.flops.forward_flops(c, int(p) + int(L))
    if not ctx.window_s:
        return None
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
