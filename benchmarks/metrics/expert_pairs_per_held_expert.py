"""Routed (token, expert) pairs an expert held here receives in one routed
layer of one decode step, mean over the decode steps that started in the
window: the program's `expert_pairs` over decode steps x routed layers x held
experts. A deployment's chip sees 16 times this from the other chips' tokens."""
from metrics._expert_steps import a_step_and_layer


def read(ctx):
    pairs = a_step_and_layer(ctx, "expert_pairs")
    return None if pairs is None else pairs / ctx.config["n_routed_experts"]
