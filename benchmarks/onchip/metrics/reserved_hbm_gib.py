"""Peak device memory that the TPU runtime reserved for the programs'
temporaries: ``peak_bytes_reserved`` of the device's allocator after the
window, in GiB.  The verify forward's full-vocabulary float32 log-probs
live here."""


def read(ctx):
    v = ctx.memory_stats.get("peak_bytes_reserved")
    return v / 2 ** 30 if v else None
