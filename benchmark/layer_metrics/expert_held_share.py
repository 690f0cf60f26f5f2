"""Expert layer: of the kept assignments the routers made over the window (every
layer, the prompt tokens of the prefill programs and the decode steps' kept
tokens, ``top_k`` each), the share that fell on an expert this chip holds (the
engine's ``assign_held`` over ``assign_all``). An even router over a deployment
of eight shares gives 12.5; what the absent chips' experts would have computed is
the rest. A program whose expert layer holds every expert counts neither."""


def read(trace, stats, record):
    every = stats.get("assign_all")
    if not every or not isinstance(every, int) or every <= 0:
        return None
    return 100.0 * float(stats.get("assign_held", 0)) / every
