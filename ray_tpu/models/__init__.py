"""Model zoo: TPU-first transformer families as pure JAX pytrees.

The reference delegates model code to torch/HF; ray_tpu ships its own
flagship decoder (Llama-family, GQA + RoPE + SwiGLU) built directly on
ray_tpu.ops kernels, with parameters as plain pytrees annotated by
logical sharding axes (ray_tpu.parallel.sharding). Layers are stacked
and scanned (`lax.scan`) so compile time is O(1) in depth; remat is a
config switch.

A second architecture sits beside it: `MLAMoE` (`models/mla_moe.py`),
multi-head latent attention with a latent paged cache and a dropless
routed feed-forward with shared experts, a leading dense layer and then
expert layers, held per layer. A config's type names its class
(`build_model`), and the serving engine asks the model it is given for
its cache and programs (`init_cache`, `prefill`, `decode_step`,
`cache_page_bytes`, `decode_attention`) and names neither class.

A third: `GQAWindowMoE` (`models/gqa_window_moe.py`), a GQA decoder whose
layers are named one by one by the config's lists: full attention or a
sliding window, each kind with its own head count, rotary scheme and cache
(`window_pages`: the ring a sliding layer keeps of a sequence), a per-head
output gate, and a dense or a routed feed-forward.

A fourth: `HybridDelta` (`models/hybrid_delta.py`), post-norm blocks whose
mixers are gated delta-rule layers (linear attention: a recurrent state of
one size a sequence, `ops/gated_delta.py`) or full multi-head attention
without rotary embedding, named layer by layer. What a model keeps of a
sequence for ever (a ring, a state) it names to the engine as
`fixed_pages`.

A fifth: `ShortcutMLAMoE` (`models/shortcut_mla_moe.py`), double layers of
two latent attentions and two dense feed-forwards with a routed
feed-forward beside them that joins at the layer's end; its router is a
softmax over experts and slots that compute nothing, and the layer is told
which of the experts it holds. The latent attention is
`models/latent.py`'s, which `MLAMoE` runs too.

A sixth: `HybridSSMMoE` (`models/hybrid_ssm_moe.py`), pre-norm blocks of
one mixer each, named layer by layer: a state-space mixer (a selective
scan, `ops/ssd.py`: a state of its own shape a sequence, which the model
prices for the allocator's fixed class), grouped-query attention without
rotary embedding, or a mixture of experts of two matrices that live in a
latent narrower than the stream, a share of them held.
"""
from ray_tpu.models.config import TransformerConfig  # noqa: F401
from ray_tpu.models.decode import (cache_page_bytes,  # noqa: F401
                                   decode_step,
                                   init_paged_cache, prefill)
from ray_tpu.models.transformer import Transformer  # noqa: F401
from ray_tpu.models.mla_moe import MLAMoE, MLAMoEConfig  # noqa: F401,E402
from ray_tpu.models.gqa_window_moe import (  # noqa: F401,E402
    GQAWindowMoE, GQAWindowMoEConfig)
from ray_tpu.models.hybrid_delta import (  # noqa: F401,E402
    HybridDelta, HybridDeltaConfig)
from ray_tpu.models.shortcut_mla_moe import (  # noqa: F401,E402
    ShortcutMLAMoE, ShortcutMLAMoEConfig)
from ray_tpu.models.hybrid_ssm_moe import (  # noqa: F401,E402
    HybridSSMMoE, HybridSSMMoEConfig)


# a dict of config fields names its class under "type"; without the key it
# is the flagship decoder's
CONFIG_TYPES = {"transformer": TransformerConfig, "mla_moe": MLAMoEConfig,
                "gqa_window_moe": GQAWindowMoEConfig,
                "hybrid_delta": HybridDeltaConfig,
                "shortcut_mla_moe": ShortcutMLAMoEConfig,
                "hybrid_ssm_moe": HybridSSMMoEConfig}
MODEL_TYPES = {MLAMoEConfig: MLAMoE, GQAWindowMoEConfig: GQAWindowMoE,
               HybridDeltaConfig: HybridDelta,
               ShortcutMLAMoEConfig: ShortcutMLAMoE,
               HybridSSMMoEConfig: HybridSSMMoE}


def model_config(model):
    """A preset's name, a dict of config fields (`"type"` names the class,
    one of `CONFIG_TYPES`; a `TransformerConfig`'s without it) or a
    config object -> the config object."""
    from ray_tpu.models.config import PRESETS
    if isinstance(model, str):
        return PRESETS[model]()
    if isinstance(model, dict):
        fields = dict(model)
        return CONFIG_TYPES[fields.pop("type", "transformer")](**fields)
    return model


def build_model(config, mesh=None):
    """The model class a config's type names, bound to `mesh`."""
    return MODEL_TYPES.get(type(config), Transformer)(config, mesh=mesh)
