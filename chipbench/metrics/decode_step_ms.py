"""Device time of the fused decode program per decode step, from the
trace (``XLA Modules`` named ``jit_fused``), over the dispatches' horizons."""
from _common import DECODE_MODULE, module_time, window_events

NAME = "decode_step_ms"
UNIT = "ms"
LAYER = "model step"


def compute(ctx):
    s, n = module_time(ctx["red"], DECODE_MODULE)
    if not n:
        return None
    hs = [e.data["h"] for e in window_events(ctx, "dispatch")]
    h = sum(hs) / len(hs) if hs else 1.0
    return s / n / h * 1e3
