"""Share of the HBM roofline the ALF Mosaic kernels reach, in %: the bytes
their calls in the traced window must move (each call's results and
operands once, unpadded, from the shapes of its HLO instruction:
``bench/flops.py``) at the chip's peak bandwidth (``bench/peaks.py``),
over the summed device time of those calls. The kernels are elementwise,
so bandwidth bounds them."""
from bench import flops, peaks


def read(ctx):
    if not ctx["job"].ode or not ctx["window_steps"]:
        return None
    calls = flops.alf_calls(ctx)
    seconds = sum(s for _, _, s in calls)
    if not seconds:
        return None
    need = sum(n * flops.hlo_call_bytes(k) for k, n, _ in calls)
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / seconds
