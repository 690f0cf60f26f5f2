"""The retention family: a pre-norm rope/GQA/SwiGLU decoder whose every mixer is
gated power retention of degree 2 (no softmax, no K/V cache: a row owns a slab
of float32 state a layer and nothing else), with per-head norms on ``q`` and
``k`` and an untied head, which ``kubedl_tpu.models.retention`` runs behind
``LlamaEngine`` without a block pool. It is served, not trained. It binds the
files that are this family and holds no code of its own:
``retention_weights.py`` (one jitted call from the seed, the gate's bias drawn
slow), ``retention_program.py`` (the bridge to ``LlamaEngine``),
``reference/retention_ref.py`` (the plain float32 state-free forward pass, its
equations, what the published file leaves unsaid and each departure in its
docstring) and, for the cell's own per-layer metrics, ``retention_costs.py``
(bytes and FLOPs from shapes)."""

from benchmark import program, retention_program
from benchmark import retention_weights as _weights
from benchmark.reference import retention_ref

enable_cache = program.enable_cache
weights = _weights.retention_weights
serve_program = retention_program.ServeProgram
logits_at = retention_ref.logits_at
