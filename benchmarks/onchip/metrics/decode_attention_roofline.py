"""Roofline share of the decode attention kernel (kernels/decode_attention):
the least time for its work, the larger of its FLOPs at the bf16 peak and
its bytes (live K/V of the active rows, plus each step's q and output) at
the HBM bandwidth, over the summed device time of the kernel's ops
(instruction names starting ``decode_attention``) in the traced batches.
Bytes bound it."""

KERNEL = "decode_attention"


def read(ctx):
    tr = ctx.trace
    if tr is None or 0 not in tr.ops:
        return None
    # a trace that lost kernel events would read a share of too little time
    if not ctx.trace_mod.whole_loops(tr, [r.steps for r in ctx.traced],
                                     ctx.config["num_hidden_layers"]):
        return None
    t = sum(e.dur for e in tr.ops[0]
            if ctx.trace_mod.short_name(e.name).startswith(KERNEL)
            and e.start >= ctx.trace_lo and e.end <= ctx.trace_hi)
    if not t:
        return None
    f = b = 0.0
    for r in ctx.traced:
        rf, rb = ctx.flops.decode_attention_cost(ctx.config, r.decode_rows)
        f, b = f + rf, b + rb
    least = max(f / ctx.peaks["bf16_flops_per_s"],
                b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
