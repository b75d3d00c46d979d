"""A configuration file (`benchmarks/configs/<name>.json`, published key
names) and the model module it names (`benchmarks/models/<model>.py`): the
one place the yardstick learns an architecture's sizes, weights, reference,
required operations and the program's own config for it. Nothing here, in
`run.py`, `tools/` or `metrics/` names a model module or branches on one."""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What a model module has to offer (benchmarks/README.md, "An architecture",
# says what each is): a module without one of these is refused when loaded,
# on the CPU, and not deep inside a run on the chip.
INTERFACE = ("Sizes", "sizes", "tiny", "weight_shapes", "program_config",
             "train_model", "logits_fn", "loss_fn", "reference_rows",
             "matmul_params", "attention_flops_per_token",
             "train_flops_per_token", "param_count")


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _model_module(name: str):
    """Imported by path, once a process: its `Sizes` is a static argument
    of jitted programs, and a second copy of the class would compile them
    again."""
    path = os.path.join(HERE, "models", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no model module {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_model_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # dataclasses look their module up there
    try:
        spec.loader.exec_module(mod)
        missing = [n for n in INTERFACE if not hasattr(mod, n)]
        if missing:
            raise AttributeError(f"model module {path} lacks {missing}")
    except BaseException:
        del sys.modules[spec.name]
        raise
    return mod


def load_model(cfg: dict):
    """The module of the model a configuration names. A file that names
    none is an error, not a default."""
    if "model" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no "
                       f"\"model\" (a file of benchmarks/models/)")
    return _model_module(cfg["model"])
