"""A reference module for the tests: ``lm``'s block, but its leaf naming
takes the leaves of any block, each named by its path inside its layer
(a leading dense layer's, a router's, the experts', a shared expert's, a
period's second sublayer's). It shows that a module names leaves the
harness has never seen, with no edit to the harness."""
from bench.reference.lm import (  # noqa: F401
    head_vjp, init_layer, init_params, job, layer_vjp, program_fields,
    required_flops_per_token, run, sizes, top_leaf)


def layer_leaf(group, index, path):
    return ".".join(path)
