"""Percent of the cache's bytes that are index keys: what the program's own
model object prices a page's index keys at (`index_page_bytes`) over what it
prices the whole page at (`cache_page_bytes`, the number the engine's page
budget stands on), at the deployment's page size and cache dtype. It is what
the indexer costs in lanes or context: 128 of 768 numbers a position and
layer here. None for a model whose class prices no index keys."""


def read(run):
    from ray_tpu.models import build_model
    dep = run["cfg"]["deployment"]
    model = build_model(run["model"].program_config(
        run["cfg"], max_seq_len=dep["context_limit"]))
    price = getattr(model, "index_page_bytes", None)
    if price is None:
        return None
    page = int(dep["page_size"])
    return 100.0 * price(page) / model.cache_page_bytes(page)
