"""Names of the train step's profiler scopes and host spans.

Each scope is a ``jax.named_scope`` ("asteroid/<name>") around one site of
the train step.  It costs nothing at run time: the name lands in the
compiled HLO's ``op_name`` metadata, so every device op of a profiler
trace can be put down to the innermost scope it was traced under
(backward and remat ops keep the scope of the forward op they come from).

============  ==========================================================
scope         site
============  ==========================================================
pipeline      the tick scan of ``pipeline.pipeline_apply``: input select,
              ``outs`` update, the scan's saved residuals
stage         ``pipeline._stage_fn``: one stage's period scan
attention     ``models.blocks.apply_layer``'s attention
mla           inside ``attention``: ``models.attention.mla_forward``, the
              latent attention (projections, rope, the blocked softmax)
mlp           ``models.blocks.apply_layer``'s dense MLP, and an expert
              layer's shared experts
router        ``models.moe.moe``: router logits, top-k, the balance loss,
              the sort of (token, expert) pairs and the weighted combine
experts       ``models.moe.moe``: the grouped matmuls of the held experts
boundary      the stage-boundary ``ppermute`` (or its compressed form)
embed         the vocab-parallel embedding and its gradient
redistribute  the ``all_to_all`` of the last stage's outputs over stages
head_ce       final norm, vocab-parallel head, chunked CE, loss reductions
grad_reduce   the gradient AllReduce (the params' varying cast, or the
              bucketed psums)
optimizer     the optimizer update
============  ==========================================================
"""

from __future__ import annotations

import jax

PREFIX = "asteroid"

PIPELINE = "pipeline"
STAGE = "stage"
ATTENTION = "attention"
MLA = "mla"
MLP = "mlp"
ROUTER = "router"
EXPERTS = "experts"
BOUNDARY = "boundary"
EMBED = "embed"
REDISTRIBUTE = "redistribute"
HEAD_CE = "head_ce"
GRAD_REDUCE = "grad_reduce"
OPTIMIZER = "optimizer"

# the scopes of every train step
ALL = (PIPELINE, STAGE, ATTENTION, MLP, BOUNDARY, EMBED, REDISTRIBUTE,
       HEAD_CE, GRAD_REDUCE, OPTIMIZER)

# the scopes of a model with latent attention or an expert layer
MODEL_SPECIFIC = (MLA, ROUTER, EXPERTS)

# host span (``jax.profiler.TraceAnnotation``) around a batch's packing and
# placement on the mesh
SHARD_BATCH_SPAN = f"{PREFIX}.shard_batch"


def scope(name: str):
    """The named scope ``asteroid/<name>``; ``name`` is one of ``ALL`` or
    ``MODEL_SPECIFIC``."""
    return jax.named_scope(f"{PREFIX}/{name}")
