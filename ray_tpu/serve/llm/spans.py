"""Names of the serving engine's spans: the contract between the engine
and the token stream, the metrics that read a profiler trace
(`benchmarks/harness/spans.py`), and PERF.md section 3 / README
"Distributed tracing". Every span but the three `llm.*` ones goes to
both sinks of `util.tracing.annotate`: the profiler's trace, on the
clock the device's trace is aligned to, and the flight recorder.
"""
from __future__ import annotations

from ray_tpu.util.tracing import annotate

# the step thread, nested as listed. A step dispatches before it reads:
# prefills, tables and the next decode step first, then the tokens of the
# decode step the call before dispatched, so everything from the fetch to
# the next dispatch runs beside a device step.
WAIT = "engine.wait_for_work"       # idle: no request waiting or running
STEP = "engine.step"                # one EngineCore.step()
# one admission: its prefill dispatched; carries `tokens`, `bucket` and
# what the model says the prefill runs (`prefill_counts`: a model with
# linear-attention layers, the `scan_chunks` of its recurrence)
PREFILL = "engine.prefill"
TABLES = "engine.page_tables"       # the decode batch's host arrays
# carries this dispatch's counts: `lanes`, `live_positions`,
# `read_positions`, `walk_blocks` and `attended_positions` (a layer whose
# cache is whole: what the lanes hold, what the kernel copies in, the
# blocks of its walk and the positions its matmuls multiply) and what the
# model says the lanes' fixed parts cost (`fixed_step_counts`): for window
# layers `window_positions_live` / `_read` / `_attended` and
# `window_walk_blocks` (a layer that holds a sequence's last positions in
# a ring), for linear-attention layers
# `state_slots` / `state_bytes` (the lanes whose state the step reads and
# writes, and the bytes moved for them)
DISPATCH = "engine.decode_dispatch"
# waits for the tokens of the step before (and this call's prefills), with
# the step just dispatched queued behind them on the device
FETCH = "engine.fetch_tokens"
EMIT = "engine.emit"                # carries the counts of the step it emits
INGEST = "engine.ingest"
PUBLISH = "stream.publish"          # child of ingest: a step's frames
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
