"""Names of the model's layers in a profiler trace.

Each is a ``jax.named_scope``: op metadata only, it puts its name on
the ``op_name`` path of every HLO operation traced inside it and
changes no operation.  Latent attention, the expert layer's routing
and its grouped expert product, the dense FFNs (a leading dense
layer's and the shared experts'), and the unembedding with its loss.
Inside an FL round they nest within the FL layer that runs the model
(``repro.core.tracing``: ``fl.local_sgd``, ``fl.bwo_fitness``,
``fl.eval``), and carry that prefix for the same accounting.

A leaf module, importing nothing, so that the model layer names its
ops without depending on the FL protocol above it.
"""
MLA = "fl.mla"                        # latent attention (models/attention)
MOE_ROUTE = "fl.moe_route"            # router, top-k, sort, gather, combine
MOE_EXPERTS = "fl.moe_experts"        # the held experts' grouped product
DENSE_FFN = "fl.dense_ffn"            # dense SwiGLU: leading layer, shared
LM_HEAD = "fl.lm_head"                # unembedding and the loss

MODEL_SCOPES = (MLA, MOE_ROUTE, MOE_EXPERTS, DENSE_FFN, LM_HEAD)
