"""Do two checkouts build the same serving programs?

    python3 tools/lowered_text.py [--tpu] <checkout> <configuration> [...]

prints, for each named file under `<checkout>/benchmarks/configs`, a sha256
and the line count of the lowered text of its class's `decode_step` and
1024-token `prefill` at the cell's shapes (lanes, pages, context). CPU
lowering is enough: the text names every operation and shape, and no weight
is made. Run it on the parent and on the change and compare: where the
hashes agree the programs are the same operations. (A program that holds a
Pallas kernel is also keyed by its source lines, which this does not see:
PERF.md section 6, PR 39.)

Lowered for the CPU a flash or paged kernel is the einsum in its place, so
the text does not hold a kernel's blocks. `--tpu` as the first argument
traces for a TPU instead (no chip: `compute_platform("tpu")`) and hashes
the traced program, the Pallas calls with their grids and bodies in it and
the source lines dropped (a call carries its call path's files and lines,
which differ between two checkouts whatever they hold); each program's
line also gives the grids of its calls over four axes (the flash
forward's: batch, heads, query blocks, key blocks), and two hashes that no
order of the equations moves (`graph_hashes`): `graph`, of the program's
outputs as a graph of equations over its inputs (an equation is its
primitive, its parameters, what it reads and what it gives; one whose
result nothing reads is no part of it), and `equations`, of all its
equations as a set, with their count. Where `sha256` differs between two
checkouts and `graph` and `equations` agree, the change wrote the same
equations in another order; where `equations` differs too, the count says
how many equations that nothing reads came or went.
"""
import functools
import hashlib
import json
import os
import re
import sys


def _clean(text: str) -> str:
    """A traced text without what two checkouts differ by whatever they
    hold: a call path's files and lines, an object's address."""
    return re.sub(r" at (0x[0-9a-f]+|[^\s\]]+:\d+)", "", text)


def graph_hashes(closed) -> dict:
    """`graph`, `equations` and `n_equations` of a `ClosedJaxpr` (the
    module's docstring). A variable's hash is its equation's (primitive,
    parameters with a nested program by its own `graph`, the hashes of
    what it reads) and its place among the equation's results; an input's
    is its place and type."""
    from jax.extend import core as jcore

    def digest(*parts) -> str:
        return hashlib.sha256("\x1f".join(map(str, parts)).encode()
                              ).hexdigest()[:24]

    def param(value):
        if isinstance(value, jcore.ClosedJaxpr):
            return walk(value.jaxpr)[0]
        if isinstance(value, jcore.Jaxpr):
            return walk(value)[0]
        if isinstance(value, (tuple, list)):
            return digest(*map(param, value))
        return _clean(str(value))

    def walk(jaxpr):
        seen = {}
        for kind, group in (("const", jaxpr.constvars), ("in", jaxpr.invars)):
            for i, var in enumerate(group):
                seen[var] = digest(kind, i, var.aval)
        nodes = []

        def read(atom):
            if isinstance(atom, jcore.Literal):
                return digest("literal", atom.val, atom.aval)
            return seen[atom]

        for eqn in jaxpr.eqns:
            node = digest(eqn.primitive.name, *(
                f"{key}={param(value)}"
                for key, value in sorted(eqn.params.items())),
                *map(read, eqn.invars), *(v.aval for v in eqn.outvars))
            nodes.append(node)
            for i, var in enumerate(eqn.outvars):
                seen[var] = digest(node, i)
        return (digest(*map(read, jaxpr.outvars)), digest(*sorted(nodes)),
                len(nodes))

    graph, equations, n = walk(closed.jaxpr)
    return {"graph": graph, "equations": equations, "n_equations": n}


def lowered(root: str, name: str, tpu: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import modelcfg
    from benchmarks.harness.weights import leaves
    from ray_tpu.models import build_model
    from ray_tpu.ops.dispatch import compute_platform
    with open(os.path.join(root, "benchmarks/configs", name + ".json")) as f:
        cfg = json.load(f)
    module, dep = modelcfg.load_model(cfg), cfg["deployment"]
    model = build_model(module.program_config(
        cfg, max_seq_len=dep["context_limit"]))
    flat, treedef = leaves(module.weight_shapes(module.sizes(cfg)))
    S = jax.ShapeDtypeStruct
    params = jax.tree_util.tree_unflatten(
        treedef, [S(shape, jnp.bfloat16) for shape, _ in flat])
    page, lanes = dep["page_size"], dep["max_batch"]
    fixed = getattr(model, "fixed_pages", lambda page: 0)(page) * lanes
    cache = jax.eval_shape(lambda: model.init_cache(
        dep["num_pages"], page, **({"fixed_pages": fixed} if fixed else {})))
    table = dep["context_limit"] // page
    with compute_platform("tpu" if tpu else None):
        # (a class with a fixed page and runs has whole runs behind it)
        table = getattr(model, "table_pages", lambda page, n: n)(page, table)
    programs = {
        "decode_step": (jax.jit(
            functools.partial(model.decode_step, page_size=page),
            donate_argnums=1), (
                params, cache, S((lanes,), jnp.int32), S((lanes,), jnp.int32),
                S((lanes, table), jnp.int32), S((lanes,), jnp.bool_))),
        "prefill_1024": (jax.jit(
            functools.partial(model.prefill, page_size=page),
            donate_argnums=4), (
                params, S((1024,), jnp.int32), S((), jnp.int32),
                S((table,), jnp.int32), cache))}
    out = {}
    for which, (fn, args) in programs.items():
        with compute_platform("tpu" if tpu else None):
            traced = fn.trace(*args)
        # a kernel's call carries its call path's files and lines
        text = (re.sub(r" at [^\s\]]+:\d+", "", str(traced.jaxpr)) if tpu
                else traced.lower().as_text())
        out[which] = {"sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
                      "lines": text.count("\n")}
        if tpu:
            out[which]["grids"] = re.findall(
                r"grid=(\(\d+, \d+, \d+, \d+\))", text)
            out[which].update(graph_hashes(traced.jaxpr))
    return out


def main():
    argv = sys.argv[1:]
    tpu = argv[:1] == ["--tpu"]
    root, *names = argv[1:] if tpu else argv
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.chdir(root)
    print(json.dumps({name: lowered(root, name, tpu) for name in names},
                     indent=1))


if __name__ == "__main__":
    main()
