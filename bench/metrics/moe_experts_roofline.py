"""Share of the bf16 peak (``bench/peaks.py``) that the held experts'
grouped products reach, in %: the FLOPs a training step requires of them
over the device time under the program's ``moe_experts`` scope, the
``ragged-dot`` Mosaic kernels included (``bench/stack_scopes.py``).
Required, per MoE layer and step: the budget's rows ``B`` (the
configuration's reference module, ``budget``), each a SwiGLU expert of
``3 D F`` weights at ``bench/flops.py``'s units of a branch (``6 (n + 1)``
with ``n`` ALF steps: forward and backward, not what MALI recomputes).
The products compute every one of the ``B`` rows, the empty ones
included, so the count is below what they execute."""
from bench import flops, peaks, stack_scopes


def read(ctx):
    sec = stack_scopes.seconds_per_step(ctx, "moe_experts")
    budget = getattr(ctx["reference"], "budget", None)
    if not sec or budget is None:
        return None
    m, job = ctx["model"], ctx["job"]
    rows = budget(ctx["tokens_per_step"] // ctx["chips"], m)
    need = (flops.branch_units(job.ode, job.n_steps) * 3 * m.d_model
            * m.expert_d_ff * rows * (m.n_layers - m.n_dense))
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / sec / peak
