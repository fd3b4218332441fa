"""Mean decode batch: ``dispatch`` events' ``n`` over the window."""
from _common import window_events

NAME = "decode_batch_mean"
UNIT = "seqs"
LAYER = "engine scheduler"


def compute(ctx):
    ns = [e.data["n"] for e in window_events(ctx, "dispatch")]
    return sum(ns) / len(ns) if ns else None
