"""The hybrid state-space family: Mamba-2 mixers beside a few attention layers
without positions, a shared SwiGLU MLP in every layer, scaled residuals and a
tied head, which ``kubedl_tpu.models.hybrid_ssm`` runs behind ``LlamaEngine``.
It is served, not trained. It binds the files that are this family and holds no
code of its own: ``hybrid_weights.py`` (one jitted call from the seed),
``hybrid_program.py`` (the bridge to ``LlamaEngine``),
``reference/hybrid_ref.py`` (the plain float32 forward pass, its equations and
each departure in its docstring) and, for the cell's own per-layer metrics,
``hybrid_costs.py`` (bytes and FLOPs from shapes)."""

from benchmark import hybrid_program, program
from benchmark import hybrid_weights as _weights
from benchmark.reference import hybrid_ref

enable_cache = program.enable_cache
weights = _weights.hybrid_weights
serve_program = hybrid_program.ServeProgram
logits_at = hybrid_ref.logits_at
