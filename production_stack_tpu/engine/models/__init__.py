"""Model zoo: decoder-only transformer families, functional JAX style.

``llama.py`` covers the Llama 2/3(.x), Mistral, Qwen2, Mixtral and Gemma
architectures (RMSNorm + rotate-half RoPE + GQA + gated MLP, optional sliding
window, softmax-routed experts).  ``sarvam_mla.py`` is latent attention (MLA,
a cache of one array a layer) over experts behind a biased sigmoid router,
held by share; ``longcat.py`` two of those attentions a layer around one
routed FFN whose softmax router also names identity experts.  ``registry.py``
maps a preset's name to its module and says
what a module must and may offer the engine.
"""

from production_stack_tpu.engine.models.registry import get_model, MODEL_REGISTRY

__all__ = ["get_model", "MODEL_REGISTRY"]
