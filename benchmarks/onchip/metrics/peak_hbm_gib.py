"""Peak device memory in buffers: ``peak_bytes_in_use`` of the device's
allocator after the window, in GiB.  On a TPU this leaves out the memory
the runtime reserves for programs' temporaries (``reserved_hbm_gib``)."""


def read(ctx):
    v = ctx.memory_stats.get("peak_bytes_in_use")
    return v / 2 ** 30 if v else None
