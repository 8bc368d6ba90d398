"""Roofline share of the latent decode kernel (``decode_attention_mla`` in
kernels/decode_attention): the least time for its work, the larger of its
FLOPs at the bf16 peak and its bytes (the live latent and rope key, 576
bf16 values per token and layer at Moonlight's sizes, read once for all
heads, plus each step's q and output) at the HBM bandwidth, over the
summed device time of the kernel's ops in the traced batches
(``harness/flops_mla_moe.py``).  Bytes bound it."""

KERNEL = "decode_attention_mla"


def read(ctx):
    tr = ctx.trace
    if tr is None or 0 not in tr.ops:
        return None
    layers = ctx.config["num_hidden_layers"]
    steps = ctx.trace_mod.decode_steps(tr, layers, kernel=KERNEL)
    # a trace that lost kernel events would read a share of too little time
    if len(steps) != len(ctx.traced) or any(
            s < r.steps for s, r in zip(steps, ctx.traced)):
        return None
    t = sum(e.dur for e in tr.ops[0]
            if ctx.trace_mod.short_name(e.name).startswith(KERNEL)
            and e.start >= ctx.trace_lo and e.end <= ctx.trace_hi)
    if not t:
        return None
    from harness import flops_mla_moe
    f = b = 0.0
    for r in ctx.traced:
        rf, rb = flops_mla_moe.mla_decode_attention_cost(ctx.config,
                                                         r.decode_rows)
        f, b = f + rf, b + rb
    least = max(f / ctx.peaks["bf16_flops_per_s"],
                b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
