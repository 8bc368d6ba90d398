"""Model FLOP utilisation of the collection step on a latent-attention MoE
model: the FLOPs that one forward over every prompt and response token of
the window's batches requires (``harness/flops_mla_moe.py``: attention in
its standard form, the dense layer, router, shared experts and the output
head over the vocabulary slice, from the configuration's sizes; the routed
experts at the program's own rate of held assignments per decoded token
and MoE layer, ``moe_held_tokens`` over ``n_generated``), over the
window's wall time times the chip's bf16 peak.  Reused and decoded tokens
count the same work.  A program that does not count held assignments
reads nothing."""


def read(ctx):
    from harness import flops_mla_moe
    c = ctx.config
    recs = ctx.records
    if not recs or not ctx.window_s or any(
            "moe_held_tokens" not in r.times for r in recs):
        return None
    decoded = sum(r.n_generated for r in recs) * flops_mla_moe.moe_layers(c)
    if not decoded:
        return None
    rate = sum(r.times["moe_held_tokens"] for r in recs) / decoded
    work = sum(flops_mla_moe.forward_flops(c, int(p) + int(L), rate)
               for r in recs for p, L in zip(r.prompt_len, r.length))
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
