"""Names of the serving engine's spans: the contract between the engine
and the token stream, the metrics that read a profiler trace
(`benchmarks/harness/spans.py`), and PERF.md section 3 / README
"Distributed tracing". Every span but the three `llm.*` ones goes to
both sinks of `util.tracing.annotate`: the profiler's trace, on the
clock the device's trace is aligned to, and the flight recorder.
"""
from __future__ import annotations

from ray_tpu.util.tracing import annotate

# an engine's construction (`LLMEngine.__init__`), nested as listed; each
# phase's seconds also stay in `engine_stats()["setup"]` and go to the
# metrics plane (`ray_tpu_llm_setup_s`), under these names
# (`setup_record.py`). A bare `EngineCore` writes its own three.
SETUP = "engine.setup"
SETUP_MODEL = "engine.setup.model"      # build_model, mesh and shardings
# the engine's own `init` or the `device_put` of given weights; `bytes`
SETUP_WEIGHTS = "engine.setup.weights"
# `init_cache`; `bytes`, `num_pages`, `fixed_pages`, `pools` (each pool's
# name and shape: pages, rings, state slots) and `pool_rows` where the
# model's pool has a row an attention (the latent caches)
SETUP_CACHE = "engine.setup.cache"
# the rest of `EngineCore.__init__`: the jitted wrappers, the walk's table
SETUP_PROGRAMS = "engine.setup.programs"
# one program built (`init`, `_step`, `_next`, `_place`, a bucket's
# `_pre`): from JAX's first sight of it to the return of the call that
# dispatched it, inside the `engine.prefill` / `engine.decode_dispatch` /
# `engine.setup.weights` that made the call. `program`, `bucket` (0: it
# has none), `rebuild` (it had been built before) on both sinks; in the
# recorder also `cache_hit`, `trace_s`, `lower_s`, `compile_s`, known when
# it ends (the rows of `engine_stats()["programs"]`)
BUILD = "engine.build_program"
# the step thread, nested as listed. A step dispatches before it reads:
# prefills, tables and the next decode step first, then the tokens of the
# decode step the call before dispatched, so everything from the fetch to
# the next dispatch runs beside a device step.
WAIT = "engine.wait_for_work"       # idle: no request waiting or running
# the step thread waits for the engine's lock, which a caller's
# `generate()` / `cancel()`, `engine_stats()` or a subscriber's backlog
# holds; written only where the lock was not free at once
LOCK_WAIT = "engine.lock_wait"
STEP = "engine.step"                # one EngineCore.step()
# one admission: its prefill dispatched; carries `tokens`, `bucket` and
# what the model says the prefill runs (`prefill_counts`: a model with
# recurrent layers, the `scan_chunks` of its recurrence; a model whose
# fixed part is a convolution's tail alone scans nothing and adds none)
PREFILL = "engine.prefill"
TABLES = "engine.page_tables"       # the decode batch's host arrays
# carries this dispatch's counts: `lanes`, `live_positions`,
# `read_positions`, `walk_blocks`, `attended_positions` and
# `walk_first_blocks_hidden` (a layer whose cache is whole: what the lanes
# hold, what the kernel copies in, the blocks of its walk, the positions
# its matmuls multiply, and the lanes whose first block the lane before
# them started) and what the
# model says the lanes' fixed parts cost (`fixed_step_counts`): for window
# layers `window_positions_live` / `_read` / `_attended` and
# `window_walk_blocks` (a layer that holds a sequence's last positions in
# a ring), for layers that hold a recurrent state (linear attention, a
# selective scan) `state_slots` / `state_bytes` (the lanes whose state the
# step reads and writes, and the bytes moved for them: a gated
# convolution's two rows a layer count here too, a state with no
# recurrence behind it); a layer that holds pages and a state (two mixers
# side by side) counts under both
DISPATCH = "engine.decode_dispatch"
# waits for the tokens of the step before (and this call's prefills), with
# the step just dispatched queued behind them on the device
FETCH = "engine.fetch_tokens"
# carries the counts of the step it emits, under the model's own names
# (`step_stats`): `moe_pairs`, `moe_experts_touched`, `moe_load_max`, and
# from a layer that holds a share of its experts `moe_zero_pairs` (choices
# of a slot that computes nothing) and `moe_away_pairs` (of an expert held
# elsewhere)
EMIT = "engine.emit"
INGEST = "engine.ingest"
# child of ingest: a step's frames, written where there is one to send,
# and the hand-off to a reader of this process behind them; `frames` (one
# a connection) and `records` (a request's part of a frame)
PUBLISH = "stream.publish"
YIELD = "engine.yield"              # the lock released between steps
# the caller's thread: generate() from entry to the engine's lock held
SUBMIT = "engine.submit"
# one request's life, flight recorder only, each written when it ends,
# all three under the trace id the request keeps
REQ_QUEUE = "llm.queue"             # submit to admission
REQ_PREFILL = "llm.prefill"         # admission to first token
REQ_DECODE = "llm.decode"           # first token to done


class span(annotate):
    kind = "llm"
