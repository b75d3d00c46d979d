"""How often bf16 serving takes another expert than the float32 reference,
and what a changed expert does to a row of logits. Chip side, one process:

    chiprun -- python tools/route_flips.py --workload <cell> --seeds 1,2

For each seed, with the benchmark's own weights for the cell's
configuration (a model module that has `route`, `_attention`, `_experts`):

- the reference's layers walked in float32 and the program's (`MLAMoE`,
  expanded attention, no cache) in bf16 over one sequence, the experts each
  chose in every expert layer compared pair by pair: the share of (token,
  slot) pairs whose expert differs, and the relative RMS error of each
  compared row's logits with and without a differing expert in that row;
- the engine's own compiled prefill and decode steps on the same tokens
  (the check `harness/serve_cell.py` makes, a row at a time): a row that is
  wrong there and right in the cache-free walk is the cache's or a
  kernel's fault, not the routing's.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prompt", type=int, default=747)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    from benchmarks.harness.cells import load_cell, prepare_device
    _, cell, cfg, mix = load_cell(a.workload)
    prepare_device(cell, bool(a.rehearse))
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import serve_cell
    from benchmarks.harness.modelcfg import load_model
    from benchmarks.harness.reference import F32, _ident, _mm, _rms
    from benchmarks.harness.weights import make_weights
    from ray_tpu.models.moe import route_topk
    from ray_tpu.ops.rope import rope_cos_sin
    from ray_tpu.serve.llm.engine import _bucket
    from ray_tpu.serve.llm.kv_cache import pages_needed
    mod = load_model(cfg)
    if a.rehearse:
        cfg = mod.tiny(cfg)
        a.prompt = min(a.prompt, 40)
    s = mod.sizes(cfg)
    seeds = [int(x) for x in a.seeds.split(",")]
    engine = serve_cell.build_engine(
        mod, cfg, lambda: make_weights(mod.weight_shapes(s), seeds[0]))
    core = engine.core
    model, c = core.model, core.config
    p, steps = a.prompt, a.steps
    n = -(-(p + steps) // 128) * 128
    rows = slice(p - 1, p + steps)

    @jax.jit
    def ref_walk(params, tokens):
        positions = jnp.arange(tokens.shape[0])
        x = params["embed"].astype(F32)[tokens]
        tops = []
        for layer in params["layers"]:
            small = {k: (v if k in mod._BIG else v.astype(F32))
                     for k, v in layer.items()}
            h = _rms(x, small["attn_norm"], s.norm_eps)
            x = x + mod._attention(s, h, small, positions, _ident, False)
            h = _rms(x, small["mlp_norm"], s.norm_eps)
            if "router" in layer:
                tops.append(mod.route(s, h, small)[0])
                x = x + mod._experts(s, h, small, _ident)
            else:
                x = x + mod._swiglu(h, small["gate"], small["up"],
                                    small["down"], _ident)
        x = _rms(x[rows], params["final_norm"].astype(F32), s.norm_eps)
        return _mm(x, params["lm_head"].astype(F32), _ident), jnp.stack(tops)

    @jax.jit
    def prog_walk(params, tokens):
        ad = c.activation_dtype
        x = params["embed"].astype(ad)[tokens][None]
        cos, sin = rope_cos_sin(jnp.arange(tokens.shape[0])[None],
                                c.qk_rope_head_dim, c.rope_theta)
        tops = []
        for layer in params["layers"]:
            h = model._norm(x, layer["attn_norm"])
            attn, _, _ = model.attention._attn_expanded(layer, h, cos,
                                                        sin)
            x = x + attn @ layer["wo"].astype(ad)
            if "router" in layer:
                h = model._norm(x, layer["mlp_norm"])
                tops.append(route_topk(
                    h[0], layer["router"], layer["router_bias"],
                    top_k=c.num_experts_per_tok,
                    norm_topk_prob=c.norm_topk_prob,
                    scale=c.routed_scaling_factor)[0])
            x, _ = model._block_ffn(layer, x)
        x = model._norm(x[0, rows], params["final_norm"])
        return ((x @ params["lm_head"].astype(ad)).astype(F32),
                jnp.stack(tops))

    def row_err(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))

    out, params = [], None
    for i, seed in enumerate(seeds):
        if i:       # the old weights first, every reference to them
            core.params = params = None
            core.params = make_weights(mod.weight_shapes(s), seed)
        params = core.params
        rng = np.random.default_rng(seed)
        toks = np.zeros((n,), np.int32)
        toks[:p + steps] = rng.integers(0, s.vocab, p + steps)
        want, ref_top = ref_walk(params, jnp.asarray(toks))
        got, prog_top = prog_walk(params, jnp.asarray(toks))
        ref_top, prog_top = np.sort(np.asarray(ref_top), -1), np.sort(
            np.asarray(prog_top), -1)
        # pairs of a token whose expert the other side did not choose
        differ = np.array([[len(set(r) - set(q)) for r, q in zip(rl, ql)]
                           for rl, ql in zip(ref_top, prog_top)])
        differ = differ[:, :p + steps]
        walk_err = row_err(got, want)
        # the engine's own programs on the same tokens
        lane = int(rng.integers(0, core.max_batch))
        with engine._lock:
            pages = core.alloc.alloc(pages_needed(p + steps, core.page_size))
            pt = np.full((core.max_pages_per_seq,), -1, np.int32)
            pt[:len(pages)] = pages
            s_pad = _bucket(p, hi=core.config.max_seq_len)
            padded = np.zeros((s_pad,), np.int32)
            padded[:p] = toks[:p]
            logits, core._cache = core._prefill_fn(s_pad)(
                params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(pt),
                core._cache)
            eng = [logits]
            B = core.max_batch
            for k in range(steps):
                tokens = np.zeros((B,), np.int32)
                positions = np.zeros((B,), np.int32)
                pts = np.full((B, core.max_pages_per_seq), -1, np.int32)
                active = np.zeros((B,), bool)
                tokens[lane], positions[lane] = toks[p + k], p + k
                pts[lane], active[lane] = pt, True
                logits, core._cache = core._decode_fn(
                    params, core._cache, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(pts),
                    jnp.asarray(active))
                eng.append(logits[lane])
            core.alloc.free(pages)
        eng_err = row_err(jnp.stack(eng), want)
        in_rows = differ[:, rows].sum(0)
        row = {"seed": seed, "prompt": p,
               "pairs_differing_share": float(differ.sum() / (
                   differ.size * c.num_experts_per_tok)),
               "rows_differing_pairs": in_rows.tolist(),
               "walk_row_error": [round(float(e), 5) for e in walk_err],
               "engine_row_error": [round(float(e), 5) for e in eng_err]}
        out.append(row)
        print(json.dumps(row), flush=True)
    engine.close()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "route_flips.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    os._exit(0)


if __name__ == "__main__":
    main()
