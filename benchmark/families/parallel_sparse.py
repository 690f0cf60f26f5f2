"""The parallel-sparse family: a decoder of parallel attention-and-experts blocks
(one mean-centring norm feeds attention, sigmoid-routed top-k experts and averaged
shared experts; one add takes all three), window layers with interleaved rope
beside full layers without positions, a tied head, and ONE CHIP'S SHARE of the
routed experts. ``kubedl_tpu.models.sparse_window`` runs it, as settings of the
model the sparse-window family runs, behind ``LlamaEngine`` on two kinds of K/V
block and the blocked arm of its attention. It is served, not trained. It binds
the files that are this family and holds no code of its own:
``parallel_sparse_weights.py`` (one jitted call from the seed),
``parallel_sparse_program.py`` (the bridge to ``LlamaEngine``),
``reference/parallel_sparse_ref.py`` (the plain float32 forward pass, its
equations and each departure in its docstring) and, for the cell's own per-layer
metrics, ``parallel_sparse_costs.py`` (bytes and FLOPs from shapes)."""

from benchmark import parallel_sparse_program, program
from benchmark import parallel_sparse_weights as _weights
from benchmark.reference import parallel_sparse_ref

enable_cache = program.enable_cache
weights = _weights.parallel_sparse_weights
serve_program = parallel_sparse_program.ServeProgram
logits_at = parallel_sparse_ref.logits_at
