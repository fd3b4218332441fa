"""The paged decode kernel's share of its roofline.

Bytes: what decode attention must move for every decoded token of the
window (its sequence's K and V at its own length, the query and the
output; ``counts.decode_attn_bytes``), so the same work is counted
whatever implements it.  Time: the ``tpu_custom_call`` ops inside the
fused decode program.  Bound: HBM bandwidth (the op count is 2 FLOPs per
byte, far under the chip's ridge)."""
import counts
from _common import DECODE_MODULE, decode_tokens

NAME = "decode_attn_roofline"
UNIT = "%"
LAYER = "kernels"


def compute(ctx):
    red = ctx["red"]
    t = sum(v for k, v in red.kernel_s.items() if DECODE_MODULE.search(k))
    if not t:
        return None
    c = ctx["config"]
    b = sum(counts.decode_attn_bytes(c, keys)
            for _, keys in decode_tokens(ctx["rec"]))
    if not b:
        return None
    return 100.0 * b / t / ctx["peaks"]["hbm_bytes_per_s"]
