"""Device idle time inside the device stages, per traced batch: the idle
gaps between the device's leaf ops that fall inside the program's
device-stage spans (``rollout.verify/compact/decode/generate/assembly``),
over the number of ``trainer.collect`` spans traced.  That is the runtime's
launch, transfer and sync cost, as against idle spent in the program's
host stages or in the harness.  Read only where the program's tracer was
on while the batches were traced."""


def read(ctx):
    tr = ctx.trace
    if tr is None or 0 not in tr.ops:
        return None
    from harness import spans
    n = len(spans.named(tr.host, (spans.COLLECT,), ctx.trace_lo,
                        ctx.trace_hi))
    if not n:
        return None
    split = spans.idle_split(tr, ctx.trace_lo, ctx.trace_hi)
    return 1e3 * split["device_stages"] / n
