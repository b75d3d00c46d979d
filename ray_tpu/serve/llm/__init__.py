"""ray_tpu.serve.llm — continuous-batching LLM inference on Serve.

The first end-to-end inference workload on the stack: engine actors
(one per replica group) run a vLLM-style continuous-batching step loop
over the ray_tpu Transformer with a paged KV cache; tokens stream to
consumers over peer-dialed push connections (r18 plane — ~0 head
frames/token); the router balances on outstanding-token depth and
fails a mid-stream generation over to a surviving replica with
exactly-once delivery.

Quickstart (byte-level "tokenizer": tiny preset vocab is 256)::

    import ray_tpu
    from ray_tpu.serve import llm

    ray_tpu.init(num_cpus=4)
    handle = llm.serve_llm(num_replicas=2)
    stream = handle.generate(list(b"the pod "), max_tokens=32)
    for token in stream:          # arrives as the engine decodes
        print(token)

A replica runs on the chips it is granted and on the CPU otherwise: on
a TPU pass ``ray_actor_options={"num_tpus": n}`` and keep replicas x n
within the host's chips; ``mesh={"dp": 1, "tp": n}`` then splits the
weights and the KV cache over them.

Tokens leave a replica one way: pushed to the subscriber over the
engine's own stream listener (`stream.py`); a replica that offers no
stream address, or refuses a subscription, is failed over like a dead one.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from ray_tpu.serve.llm.engine import (EngineCore,  # noqa: F401
                                      LLMEngine)
from ray_tpu.serve.llm.kv_cache import (PageAllocator,  # noqa: F401
                                        pages_from_budget,
                                        pages_needed)
from ray_tpu.serve.llm.router import (LLMHandle,  # noqa: F401
                                      TokenStream)
from ray_tpu.serve.llm.stream import STREAM_STATS  # noqa: F401


def serve_llm(name: str = "llm", model: Any = "tiny",
              weights: Any = None, num_replicas: int = 2,
              mesh: Optional[Dict[str, int]] = None,
              num_pages: int = 0, page_size: int = 0,
              max_batch: int = 0, kv_budget_bytes: int = 0,
              seed: int = 0,
              max_ongoing_requests: int = 32,
              ray_actor_options: Optional[dict] = None,
              autoscaling_config: Any = None,
              broadcast_weights: bool = True) -> LLMHandle:
    """Deploy an LLM engine deployment and return its routing handle.

    `weights` may be a params pytree (put once, delivered to every
    cold replica through the object plane after an r12 broadcast
    pre-seeds all nodes), an ObjectRef, or None (each replica inits
    identically from `seed` — fine for tests, wasteful for real
    weights).

    `ray_actor_options={"num_tpus": n}` gives each replica n chips; a
    replica without a grant runs on the CPU (`engine_stats()` says
    where each runs). `mesh` must fit the replica's devices.
    """
    import ray_tpu
    from ray_tpu import serve

    ref = weights
    if weights is not None and not hasattr(weights, "object_id"):
        ref = ray_tpu.put(weights)
    if ref is not None and broadcast_weights and num_replicas > 1:
        # cut-through relay: seed every node's store before the
        # replicas cold-start, so N replicas pull locally instead of
        # N point-to-point transfers from the owner
        try:
            from ray_tpu._private import context as _context
            ctx = _context.maybe_ctx()
            bcast = getattr(ctx, "broadcast_object", None)
            if bcast is not None:
                bcast(ref.object_id)
        except BaseException:
            pass

    dep = serve.deployment(
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=dict(ray_actor_options or {}),
        autoscaling_config=autoscaling_config,
    )(LLMEngine).options(name=name)
    app = dep.bind(model=model, weights=ref, mesh=mesh,
                   num_pages=num_pages, page_size=page_size,
                   max_batch=max_batch,
                   kv_budget_bytes=kv_budget_bytes, seed=seed)
    serve.run(app, name=name)
    return LLMHandle(name)


def get_llm_handle(name: str = "llm") -> LLMHandle:
    return LLMHandle(name)
