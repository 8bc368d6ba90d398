"""Share of the decode loop's row-steps that generate a token, on the reuse
path: the traced batches' generated tokens over (rows x the steps their
decode loops ran).  The trip counts come from the trace (the decode
attention kernel's events over the layer count), so an engine that stops
early or refills finished rows moves this; a fixed batch that runs until
its slowest row is done wastes the rest."""


def read(ctx):
    if ctx.trace is None or not ctx.traced:
        return None
    if not all(r.times.get("one_pass") == 1.0 for r in ctx.traced):
        return None
    steps = ctx.trace_mod.decode_steps(ctx.trace,
                                       ctx.config["num_hidden_layers"])
    if len(steps) != len(ctx.traced):
        return None
    slots = sum(len(r.length) * s for r, s in zip(ctx.traced, steps))
    if not slots:
        return None
    return 100.0 * sum(int(r.generated.sum()) for r in ctx.traced) / slots
