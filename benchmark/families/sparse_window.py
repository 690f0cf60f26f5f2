"""The sparse-window family: a decoder whose every layer has a dropless top-k
routed expert layer, window-attention layers beside full-attention layers with a
rotary table a kind, and an untied head, which ``kubedl_tpu.models.sparse_window``
runs behind ``LlamaEngine`` on two kinds of K/V block. It is served, not trained.
It binds the files that are this family and holds no code of its own:
``sparse_weights.py`` (one jitted call from the seed), ``sparse_program.py`` (the
bridge to ``LlamaEngine``), ``reference/sparse_window_ref.py`` (the plain float32
forward pass, its equations and each departure in its docstring) and, for the
cell's own per-layer metrics, ``sparse_costs.py`` (bytes and FLOPs from shapes)."""

from benchmark import program, sparse_program
from benchmark import sparse_weights as _weights
from benchmark.reference import sparse_window_ref

enable_cache = program.enable_cache
weights = _weights.sparse_weights
serve_program = sparse_program.ServeProgram
logits_at = sparse_window_ref.logits_at
