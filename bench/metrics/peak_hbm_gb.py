"""Peak device memory after the window, in GB (1e9 bytes): the largest
over the cell's chips of ``memory_stats()["peak_bytes_in_use"]`` (the
buffers: weights, optimizer state, batches, outputs) plus
``["peak_bytes_reserved"]`` (the region the TPU runtime reserves for a
program's temporaries, which ``peak_bytes_in_use`` leaves out)."""


def read(ctx):
    peak = ctx.get("peak_bytes")
    return None if peak is None else peak / 1e9
