"""Model FLOP utilization of the training step in the traced window, in %:
the FLOPs per token the forward and backward passes require (not what
MALI recomputes; the configuration's reference module counts them, as
``bench/flops.py`` says) times the window's tokens, over the window's time
and the chips' bf16 peak (``bench/peaks.py``)."""
from bench import peaks


def read(ctx):
    if not ctx["window_steps"] or not ctx.get("trace"):
        return None
    per_token = ctx["reference"].required_flops_per_token(
        ctx["model"], ctx["job"], ctx["seq_len"])
    rate = (per_token * ctx["window_steps"] * ctx["tokens_per_step"]
            / ctx["window_s"])
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate / (ctx["chips"] * peak)
