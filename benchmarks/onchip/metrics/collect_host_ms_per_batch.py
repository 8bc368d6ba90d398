"""The collection step's own host time per traced batch: the program's
``trainer.collect`` span minus the part its device-stage spans
(``rollout.verify/compact/decode/generate/assembly``, each ending at a
block_until_ready) cover, read from the trace's host plane.  Unlike
``host_ms_per_batch`` it leaves out the harness's own work and is read on
the fresh path too.  Read only where the program's tracer was on while the
batches were traced."""


def read(ctx):
    if ctx.trace is None:
        return None
    from harness import spans
    host = spans.collect_host_s(ctx.trace, ctx.trace_lo, ctx.trace_hi)
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
