"""Seconds of set-up spent compiling or loading programs: the program's
``compiles.{trace,lower,backend,cache_load}_s`` counters (repro.obs's
compile listener) from process start to the first measured batch, from
the registry snapshot the run takes there (``ctx.setup_counters``).  A run
that takes no snapshot, or a program without the counters, reads
nothing."""

KINDS = ("trace", "lower", "backend", "cache_load")


def read(ctx):
    counters = getattr(ctx, "setup_counters", None)
    if not counters:
        return None
    keys = [f"compiles.{k}_s" for k in KINDS]
    if not any(k in counters for k in keys):
        return None
    return sum(counters.get(k, 0.0) for k in keys)
