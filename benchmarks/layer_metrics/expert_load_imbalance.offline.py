"""How far the busiest held expert runs over the mean, over the window:
100 x (sum over dispatches and routed layers of the busiest held expert's
tokens) / (the same sum of the mean over the held experts) - 100, from the
scheduler's ``moe_expert_tokens_max`` and ``moe_expert_tokens_mean`` (a
decode block adds up its steps).  0 is even routing; a grouped product's
time follows its busiest expert only where experts run side by side, here
it says how far random weights' routing is from a trained model's.  A
program without routed-expert counters (a dense model, the parent): None."""


def read(facts):
    c = facts["counters"]
    mean = c.get("moe_expert_tokens_mean")
    if not mean:
        return None
    return 100.0 * c["moe_expert_tokens_max"] / mean - 100.0
