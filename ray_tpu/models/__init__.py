"""Model zoo: TPU-first decoder families as pure JAX pytrees.

The reference delegates model code to torch/HF; ray_tpu ships its own
decoders built directly on `ray_tpu.ops` kernels, with parameters as plain
pytrees. `MODELS` is the table of them: a config's type names its class
(`model_config`, `build_model`), and the serving engine asks the model it
is given what `models.paged.PagedDecoder` writes down and names no class.

`Transformer` (`transformer.py`, its serving programs in `decode.py`) is
the flagship: Llama-family, GQA + RoPE + SwiGLU, layers stacked and scanned
(`lax.scan`, so compile time is O(1) in depth), annotated by logical
sharding axes (`ray_tpu.parallel.sharding`) and trained on a mesh; what a
rematted layer keeps is a rule over the step's shapes (`remat_plan`). The
other classes serve on one device and hold their layers one by one; each
module's docstring has its mathematics. A layer's mixer answers for itself
(`paged.Mixer`: its leaves, the pools it keeps of a sequence, the kernel
that reads them, its three forwards): grouped-query attention in `gqa.py`,
latent attention in `latent.py`, the state-space mixer in `ssm.py`, the
delta rules, the gated short convolution and the sparse latent attention
beside the one class that has each. A served class is its config, a table
of layers built in its `__init__` (which mixers read the normed stream, the
feed-forward that follows: `moe.py` has the routed ones) and what is its
own; `paged.py` walks the table for every program and every ask.
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
from ray_tpu.models.hybrid_kda_moe import (  # noqa: F401,E402
    HybridKDAMoE, HybridKDAMoEConfig)
from ray_tpu.models.parallel_hybrid import (  # noqa: F401,E402
    ParallelHybrid, ParallelHybridConfig)
from ray_tpu.models.gated_conv_moe import (  # noqa: F401,E402
    GatedConvMoE, GatedConvMoEConfig)
from ray_tpu.models.sparse_mla_moe import (  # noqa: F401,E402
    SparseMLAMoE, SparseMLAMoEConfig)
from ray_tpu.models.sparse_window_mla_moe import (  # noqa: F401,E402
    SparseWindowMLAMoE, SparseWindowMLAMoEConfig)


# name -> (config class, model class). A dict of config fields names its
# class under "type"; without the key it is the flagship decoder's
MODELS = {"transformer": (TransformerConfig, Transformer),
          "mla_moe": (MLAMoEConfig, MLAMoE),
          "gqa_window_moe": (GQAWindowMoEConfig, GQAWindowMoE),
          "hybrid_delta": (HybridDeltaConfig, HybridDelta),
          "shortcut_mla_moe": (ShortcutMLAMoEConfig, ShortcutMLAMoE),
          "hybrid_ssm_moe": (HybridSSMMoEConfig, HybridSSMMoE),
          "hybrid_kda_moe": (HybridKDAMoEConfig, HybridKDAMoE),
          "parallel_hybrid": (ParallelHybridConfig, ParallelHybrid),
          "gated_conv_moe": (GatedConvMoEConfig, GatedConvMoE),
          "sparse_mla_moe": (SparseMLAMoEConfig, SparseMLAMoE),
          "sparse_window_mla_moe": (SparseWindowMLAMoEConfig,
                                    SparseWindowMLAMoE)}


def model_config(model):
    """A preset's name, a dict of config fields (`"type"` names the class,
    a key of `MODELS`; a `TransformerConfig`'s without it) or a config
    object -> the config object."""
    from ray_tpu.models.config import PRESETS
    if isinstance(model, str):
        return PRESETS[model]()
    if isinstance(model, dict):
        fields = dict(model)
        return MODELS[fields.pop("type", "transformer")][0](**fields)
    return model


def build_model(config, mesh=None):
    """The model class a config's type names, bound to `mesh`."""
    return dict(MODELS.values()).get(type(config), Transformer)(
        config, mesh=mesh)
