"""KV pages in use: 1 - free / pool pages, from ``load_stats()`` after
every tick of the window, averaged."""
from _common import in_window

NAME = "kv_used_share"
UNIT = "%"
LAYER = "KV manager"


def compute(ctx):
    rec = ctx["rec"]
    xs = [u for t, u in rec.kv_used if in_window(rec, t)]
    return 100.0 * sum(xs) / len(xs) if xs else None
