"""Tokens per held expert per layer per decode step: the routed (token,
expert) pairs of the window's ``moe_route`` decode events over the experts
held (``num_experts``) x layers x decode steps of the same events.  The
uniform expectation is the decode batch x top k / the router's experts."""
from _common import window_events

NAME = "moe_tokens_per_expert"
UNIT = "tokens"
LAYER = "expert layer"


def compute(ctx):
    evs = [e.data for e in window_events(ctx, "moe_route")
           if e.data["phase"] == "decode"]
    steps = sum(e["steps"] for e in evs)
    if not steps:
        return None
    c = ctx["config"]
    routed = sum(e["routed_here"] for e in evs)
    return routed / (c["num_experts"] * c["num_hidden_layers"] * steps)
