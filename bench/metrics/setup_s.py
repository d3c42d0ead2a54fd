"""Seconds from the start of the process to the first timed step: imports,
runtime start, weights, the warm-up steps (the first compiles or loads
the compiled step from the cache). The time spent copying the first
steps' batches, weights and moments aside for the comparison is left
out."""


def read(ctx):
    return ctx["setup_s"]
