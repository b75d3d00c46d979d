"""How far the sets a program's indexer chooses in bfloat16 are from the
reference's in float32, at the published widths, on the chip:

    python3 benchmarks/tools/dsa_sets.py --workload <cell> --seeds 1,2 \
        --tokens 4096

One layer's indexer (the first layer's: its input is the embedding alone,
so both sides see the same numbers) on `--tokens` seeded tokens: the
program's pieces (`SparseMLAMoE._index_key`, `_index_query`,
`ops.sparse_attention.prefill_keep_mask`, bfloat16 projections, products
summed in float32) against the model module's (`index_parts`,
`selected_mask`: float32 at precision `highest`, `jax.lax.top_k`). Prints,
over the queries past `index_topk`, the share whose sets differ at all, the
positions that differ a query (in one set and not the other, halved: a
swapped key counts once) at the mean, the median and the largest, and the
share of queries whose program set holds more than `index_topk` keys (a tie
at the threshold). A near-tie at the `index_topk`-th score that bfloat16
breaks otherwise than float32 swaps one key of 2,048.
"""
import argparse
import json
import os

from _common import ROOT

from benchmarks.harness.cells import load_cell, prepare_device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    _, cell, cfg, _ = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.harness.modelcfg import load_model
    from benchmarks.harness.reference import F32, _rms
    from benchmarks.harness.weights import make_weights
    from ray_tpu.models import build_model
    from ray_tpu.ops.rope import rope_cos_sin
    from ray_tpu.ops.sparse_attention import prefill_keep_mask
    mod = load_model(cfg)
    if a.rehearse:
        cfg = mod.tiny(cfg)
    sz = mod.sizes(cfg)
    model = build_model(mod.program_config(cfg, max_seq_len=a.tokens))
    c, s = model.config, a.tokens
    shapes = mod.weight_shapes(sz)
    shapes = {"embed": shapes["embed"], "layer": shapes["layers"][0]}
    rows = []
    for seed in [int(x) for x in a.seeds.split(",")]:
        params = make_weights(shapes, seed)
        layer = params["layer"]
        toks = np.random.default_rng(seed).integers(0, sz.vocab, s)

        @jax.jit
        def program(params):
            layer = params["layer"]
            x = params["embed"].astype(c.activation_dtype)[toks]
            h = model._norm(x[None], layer["attn_norm"])
            cos, sin = rope_cos_sin(jnp.arange(s)[None],
                                    c.qk_rope_head_dim, c.rope_theta)
            c_q, _ = model._q_latent(layer, h)
            q_idx, w = model._index_query(layer, h, c_q, cos, sin)
            k_idx = model._index_key(layer, h, cos, sin)
            return prefill_keep_mask(q_idx[0], w[0], k_idx[0],
                                     c.index_topk) != 0

        @jax.jit
        def reference(params):
            layer = jax.tree.map(lambda v: v.astype(F32), params["layer"])
            x = params["embed"].astype(F32)[toks]
            h = _rms(x, layer["attn_norm"], sz.norm_eps)
            c_q = _rms(jnp.matmul(h, layer["wq_a"],
                                  precision=jax.lax.Precision.HIGHEST),
                       layer["q_norm"], sz.norm_eps)
            return mod.selected_mask(
                sz, *mod.index_parts(sz, h, c_q, layer, jnp.arange(s)))

        got, want = np.asarray(program(params)), np.asarray(reference(params))
        past = np.arange(s) >= sz.index_topk
        differ = (got != want).sum(axis=1)[past] / 2.0
        row = {"seed": seed, "tokens": s, "queries_past_topk": int(past.sum()),
               "sets_that_differ_share": float((differ > 0).mean()),
               "positions_differ_mean": float(differ.mean()),
               "positions_differ_median": float(np.median(differ)),
               "positions_differ_max": float(differ.max()),
               "sets_over_topk_share": float(
                   (got.sum(axis=1)[past] > sz.index_topk).mean())}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params
    out = os.path.join(ROOT, "chiprun_out", f"dsa_sets.{a.workload}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
