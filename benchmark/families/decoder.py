"""The decoder family: a plain pre-norm RMSNorm / rope / GQA / SwiGLU decoder,
which ``kubedl_tpu.models.llama`` runs. It binds the files that are this family
and holds no code of its own: ``weights.py`` (shapes by
``kernel_costs.sizes_of``), ``program.py`` (the bridge to ``LlamaEngine`` and
``Trainer``), ``reference/decoder_ref.py`` and ``reference/train_ref.py``."""

from benchmark import program
from benchmark import weights as _weights
from benchmark.reference import decoder_ref, train_ref

enable_cache = program.enable_cache
weights = _weights.decoder_weights
serve_program = program.ServeProgram
train_program = program.TrainProgram
logits_at = decoder_ref.logits_at
train_follow = train_ref.follow
