"""Expert layer: the busiest expert's kept tokens over the mean expert's, over
the window and all layers (the engine's ``expert_tokens``, by layer and
expert: the prompt tokens of the prefill programs, where the product is
grouped, and the decode steps' kept tokens, a few in a hundred of them): what
a grouped product's longest group is to its mean. 1 is an even load. A program
without routed experts reports no counts."""


def read(trace, stats, record):
    table = stats.get("expert_tokens")
    if not table or not isinstance(table, list):
        return None
    counts = [n for layer in table for n in layer]
    if not counts or sum(counts) <= 0:
        return None
    return max(counts) * len(counts) / sum(counts)
