"""The one general traffic generator. A traffic mix is a data file under
`benchmarks/traffic/` naming a `kind` below and its parameters; nothing of a
mix lives in code.

What a run offers is fixed by the file: arrival instants, prompt and output
lengths and their order come from the file's own `schedule_seed` and are
byte-identical in every run. `--seed` chooses only the token ids of the
prompts (and, elsewhere, the weights), so every seed weighs the same.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int          # position in the schedule; keys the prompt's tokens
    due_s: float        # open loop: seconds after the window opens; else 0
    prompt_len: int
    max_tokens: int
    client: int = 0     # closed loop: which client sends it


def load_traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r} is not one of "
                         f"{sorted(KINDS)}")
    return mix


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """n lengths: a log-normal around `median` with shape `sigma`, held to
    [`min`, `max`] by clipping; or the constant `exactly`."""
    if "exactly" in spec:
        return np.full(n, int(spec["exactly"]), np.int64)
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _rng(mix: dict) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(int(mix["schedule_seed"])))


def open_loop_schedule(mix: dict) -> List[Request]:
    """Poisson arrivals at `rate_rps`: unit-rate gaps drawn once and
    stretched by the rate, so a sweep offers this very schedule faster or
    slower. All `num_requests` are drawn whatever the window; a run sends
    those due inside it."""
    rng = _rng(mix)
    n = int(mix["num_requests"])
    unit_due = np.cumsum(rng.exponential(1.0, n))
    prompts = _lengths(rng, mix["prompt_tokens"], n)
    outputs = _lengths(rng, mix["output_tokens"], n)
    rate = float(mix["rate_rps"])
    return [Request(i, float(unit_due[i] / rate), int(prompts[i]),
                    int(outputs[i])) for i in range(n)]


def closed_loop(mix: dict) -> List[Request]:
    """`clients` callers, each sending its own fixed list one after the
    other; a client that runs out starts its list again."""
    rng = _rng(mix)
    clients, per = int(mix["clients"]), int(mix["requests_per_client"])
    n = clients * per
    prompts = _lengths(rng, mix["prompt_tokens"], n)
    outputs = _lengths(rng, mix["output_tokens"], n)
    return [Request(i, 0.0, int(prompts[i]), int(outputs[i]), client=i // per)
            for i in range(n)]


def train_steps(mix: dict) -> List[Request]:
    """`distinct_batches` batches of `batch` x `seq_len` packed tokens that
    the steps cycle through; one Request stands for one batch."""
    b, s = int(mix["batch"]), int(mix["seq_len"])
    return [Request(i, 0.0, b * s, 0)
            for i in range(int(mix["distinct_batches"]))]


KINDS = {"open_loop_schedule": open_loop_schedule,
         "closed_loop": closed_loop,
         "train_steps": train_steps}


def schedule(mix: dict) -> List[Request]:
    return KINDS[mix["kind"]](mix)


def prompt_tokens(requests: List[Request], vocab_size: int,
                  seed: int) -> List[np.ndarray]:
    """Token ids of each request's prompt, from `--seed` alone; lengths stay
    the schedule's."""
    rng = np.random.Generator(np.random.PCG64([int(seed), 0x70726f6d]))
    flat = rng.integers(0, vocab_size, sum(r.prompt_len for r in requests),
                        dtype=np.int32)
    return np.split(flat, np.cumsum([r.prompt_len for r in requests])[:-1])


def totals(requests: List[Request], seconds: float) -> dict:
    """What the schedule offers in a window of `seconds` (open loop: what is
    due inside it; other kinds: the whole list)."""
    due = [r for r in requests if r.due_s < seconds]
    return {"requests": len(due),
            "prompt_tokens": sum(r.prompt_len for r in due),
            "output_tokens": sum(r.max_tokens for r in due)}
