"""A serving cell: one `LLMEngine` (step thread and `TokenStreamServer`)
inside this process, which holds the chip; a load generator whose clients
subscribe to token streams the way `serve_llm`'s clients do; a fixed window;
then the comparison with the plain reference.

Everything here stays off the timed path's arithmetic: the engine is the
program's, the clock readings are the collector's.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import stats, traffic
from benchmarks.harness.cells import CompileCounter
from benchmarks.harness.modelcfg import load_model

NOW = time.monotonic          # the engine's own clock (queue waits)


class Collector:
    """The one sink every stream frame lands in, stamped on arrival on the
    stream connection's reader thread."""

    def __init__(self):
        self.lock = threading.Lock()
        self.arrivals: Dict[str, List[float]] = {}   # rid -> token times
        self.done: Dict[str, float] = {}
        self.errors: Dict[str, str] = {}
        self.on_done = None                          # closed loop's hook
        self.first_seen = threading.Condition(self.lock)

    def put(self, msg: dict) -> None:
        now = NOW()
        rid = msg.get("req") or msg.get("rid")
        with self.lock:
            if msg.get("type") == "llm_closed" or msg.get("unknown"):
                self.errors[rid] = msg.get("err") or "stream lost"
                self.done.setdefault(rid, now)
            else:
                self.arrivals.setdefault(rid, []).extend(
                    [now] * len(msg.get("toks", ())))
                if msg.get("err"):
                    self.errors[rid] = str(msg["err"])[-400:]
                if msg.get("done"):
                    self.done.setdefault(rid, now)
            finished = rid in self.done
            self.first_seen.notify_all()
        if finished and self.on_done is not None:
            self.on_done(rid)


class Sent:
    def __init__(self, request, due_t, sent_t):
        self.request, self.due_t, self.sent_t = request, due_t, sent_t
        self.accepted_t = None      # when generate() returned


def build_engine(model, cfg: dict, params_fn):
    """The program's engine at the deployment the file states, holding the
    benchmark's own weights."""
    from ray_tpu.serve.llm.engine import LLMEngine
    dep = cfg["deployment"]
    pcfg = model.program_config(cfg, max_seq_len=dep["context_limit"])
    engine = LLMEngine(model=pcfg, seed=0, num_pages=dep["num_pages"],
                       page_size=dep["page_size"],
                       max_batch=dep["max_batch"])
    if engine._stream is None:
        raise RuntimeError("the cell measures the push stream; "
                           "CONFIG.llm_stream is off")
    # the engine can only make weights of its own; drop them before the
    # benchmark's are made so the two never lie on the chip together
    engine.core.params = None
    engine.core.params = params_fn()
    return engine


def _send(engine, client, collector, sent: Dict[str, Sent], rid: str,
          request, prompt: np.ndarray, due_t: float, stop) -> None:
    sent_t = NOW()
    with collector.lock:
        sent[rid] = Sent(request, due_t, sent_t)
    acc = engine.generate(prompt.tolist(), max_tokens=request.max_tokens,
                          stop=stop, rid=rid)
    sent[rid].accepted_t = NOW()
    ok = client.subscribe(tuple(acc["stream"]), rid, acc["incarnation"],
                          acc["attempt"], 0, collector)
    if not ok:
        collector.put({"type": "llm_closed", "rid": rid})


def warm_up(engine, client, requests, vocab: int, timeout_s: float) -> None:
    """One request per prefill bucket the cell's prompts fall into, two
    tokens each: compiles (or loads) every prefill program, the decode
    program and the small programs beside them (the next step's tokens,
    picked on the device since PR 30, and a lane's placement), and walks
    the stream path once."""
    from ray_tpu.serve.llm.engine import _bucket
    limit = engine.core.config.max_seq_len
    buckets = sorted({_bucket(r.prompt_len, hi=limit) for r in requests})
    collector = Collector()
    sent: Dict[str, Sent] = {}
    for b in buckets:
        n = min(b, limit - 2)
        req = traffic.Request(-1, 0.0, n, 2)
        _send(engine, client, collector, sent, f"warm-{b}", req,
              np.full(n, 1 % vocab, np.int32), NOW(), ())
    deadline = NOW() + timeout_s
    with collector.lock:
        while len(collector.done) < len(buckets):
            if NOW() > deadline or collector.errors:
                raise RuntimeError(
                    f"warm-up did not finish: done {sorted(collector.done)}"
                    f" errors {collector.errors}")
            collector.first_seen.wait(0.5)
    engine.check_health()


def offer_open_loop(engine, client, collector, sent, requests, prompts,
                    t0: float, seconds: float, stop, label: str) -> None:
    """Send each request when it is due, whether or not earlier ones have
    finished; a request due after the window is not sent."""
    for req, prompt in zip(requests, prompts):
        if req.due_s >= seconds:
            break
        due_t = t0 + req.due_s
        delay = due_t - NOW()
        if delay > 0:
            time.sleep(delay)
        _send(engine, client, collector, sent, f"{label}-{req.index}", req,
              prompt, due_t, stop)


def offer_closed_loop(engine, client, collector, sent, requests, prompts,
                      t0: float, seconds: float, stop, label: str) -> None:
    """Each client sends its next request when its last one completes,
    until the window closes. One sender thread serves all clients."""
    by_client: Dict[int, List[int]] = {}
    for i, r in enumerate(requests):
        by_client.setdefault(r.client, []).append(i)
    cursor = {c: 0 for c in by_client}
    owner: Dict[str, int] = {}
    ready: "queue.Queue[int]" = queue.Queue()
    collector.on_done = lambda rid: ready.put(owner.get(rid, -1))
    for c in sorted(by_client):
        ready.put(c)
    end_t = t0 + seconds
    while True:
        try:
            c = ready.get(timeout=max(0.0, end_t - NOW()))
        except queue.Empty:
            break
        if NOW() >= end_t:
            break
        if c < 0:
            continue
        mine = by_client[c]
        i = mine[cursor[c] % len(mine)]
        rid = f"{label}-{c}-{cursor[c]}"
        cursor[c] += 1
        owner[rid] = c
        _send(engine, client, collector, sent, rid, requests[i], prompts[i],
              NOW(), stop)
    collector.on_done = None


OFFER = {"open_loop_schedule": offer_open_loop,
         "closed_loop": offer_closed_loop}


def drain(engine, client, collector, sent, open_loop: bool,
          timeout_s: float) -> None:
    """After the window: an open loop waits until every request it sent has
    its first token (each is measured to it); then whatever still runs is
    cancelled, its tokens past the window being nobody's."""
    deadline = NOW() + timeout_s
    with collector.lock:
        while open_loop and NOW() < deadline and any(
                not collector.arrivals.get(rid) and rid not in collector.done
                for rid in sent):
            collector.first_seen.wait(0.2)
        open_rids = [rid for rid in sent if rid not in collector.done]
    for rid in open_rids:
        client.unsubscribe(rid)
        engine.cancel(rid)


def instrument(engine, samples: dict):
    """Trace runs only: host spans around the calls into each layer, from
    the benchmark's side (the program has none on this clock yet), and the
    decode batch of every step."""
    import jax
    core = engine.core
    span = jax.profiler.TraceAnnotation

    def wrap(obj, name, label, after=None):
        orig = getattr(obj, name)

        def wrapped(*a, **k):
            with span(label):
                out = orig(*a, **k)
            if after is not None:
                after(out)
            return out
        setattr(obj, name, wrapped)

    def lanes(events):
        samples["lanes"].append(
            (NOW(), len(core._running) + sum(1 for e in events if e["done"])))

    wrap(core, "step", "bench.engine_step", lanes)
    wrap(core, "_decode_fn", "bench.decode_dispatch")
    orig_prefill = core._prefill_fn

    def prefill_fn(s_pad):
        fn = orig_prefill(s_pad)

        def call(*a, **k):
            with span("bench.prefill_dispatch"):
                return fn(*a, **k)
        return call
    core._prefill_fn = prefill_fn
    wrap(engine, "_ingest", "bench.ingest")
    wrap(engine._stream, "publish", "bench.stream_publish")


def window_metrics(collector, sent, t0, seconds) -> dict:
    """Arithmetic over what the collector stamped, all of it inside
    [t0, t0 + seconds] except that a request due inside the window is
    measured to its first token wherever that falls."""
    end_t = t0 + seconds
    ttft, gaps, lags, tokens, submits = [], [], [], 0, []
    no_first = 0
    for rid, s in sent.items():
        arr = collector.arrivals.get(rid, [])
        lags.append(s.sent_t - s.due_t)
        if s.accepted_t is not None:
            submits.append(s.accepted_t - s.sent_t)
        if arr:
            ttft.append(arr[0] - s.due_t)
        else:
            no_first += 1
        tokens += sum(1 for t in arr if t0 <= t <= end_t)
        gaps.extend(g for g, t in zip(stats.token_gaps(arr), arr[1:])
                    if t <= end_t)
    return {"ttft_s": ttft, "gap_s": gaps, "lag_s": lags,
            "submit_s": submits, "tokens_in_window": tokens,
            "no_first_token": no_first}


def check_against_reference(engine, model, sz, mix, params, requests,
                            prompts, seed: int, window_requests: int):
    """Prefill-then-decode logits of the engine's own compiled programs on a
    seeded sample of the cell's requests, against the reference's full
    forward. Returns one relative RMS error per sampled request."""
    import jax.numpy as jnp
    from ray_tpu.serve.llm.engine import _bucket
    from ray_tpu.serve.llm.kv_cache import pages_needed
    from benchmarks.harness.reference import rel_rms

    core = engine.core
    steps = int(mix["check_decode_steps"])
    n_check = min(int(mix["check_requests"]), core.max_batch)
    rng = np.random.Generator(np.random.PCG64([int(seed), 0x63686b]))
    pool = max(window_requests, n_check)
    picks = rng.choice(min(pool, len(requests)), n_check, replace=False)
    lanes = rng.choice(core.max_batch, n_check, replace=False)
    longest = max(r.prompt_len for r in requests) + steps
    ref_len = -(-longest // 128) * 128
    seqs, tables, got = [], [], []
    with engine._lock:
        if core.has_work:
            raise RuntimeError("the engine is not idle for the check")
        for i in picks:
            extra = rng.integers(0, sz.vocab, steps, dtype=np.int32)
            toks = np.concatenate([prompts[i], extra])
            p = requests[i].prompt_len
            pages = core.alloc.alloc(pages_needed(p + steps, core.page_size))
            pt = np.full((core.max_pages_per_seq,), -1, np.int32)
            pt[:len(pages)] = pages
            s_pad = _bucket(p, hi=core.config.max_seq_len)
            padded = np.zeros((s_pad,), np.int32)
            padded[:p] = toks[:p]
            logits, core._cache = core._prefill_fn(s_pad)(
                core.params, jnp.asarray(padded), jnp.int32(p),
                jnp.asarray(pt), core._cache)
            seqs.append((toks, p, pages))
            tables.append(pt)
            got.append([logits])
        B = core.max_batch
        for k in range(steps):
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            pts = np.full((B, core.max_pages_per_seq), -1, np.int32)
            active = np.zeros((B,), bool)
            for (toks, p, _), pt, lane in zip(seqs, tables, lanes):
                tokens[lane], positions[lane] = toks[p + k], p + k
                pts[lane], active[lane] = pt, True
            logits, core._cache = core._decode_fn(
                core.params, core._cache, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(pts),
                jnp.asarray(active))
            for j, lane in enumerate(lanes):
                got[j].append(logits[lane])
        for _, _, pages in seqs:
            core.alloc.free(pages)
    errors = []
    for (toks, p, _), rows in zip(seqs, got):
        padded = np.zeros((ref_len,), np.int32)
        padded[:len(toks)] = toks
        want = model.reference_rows(sz, params, jnp.asarray(padded),
                                    jnp.int32(p - 1), steps + 1)
        errors.append(rel_rms(jnp.stack(rows), want))
    return errors, [int(requests[i].prompt_len) for i in picks]


class Served:
    """The system under test, warmed up, with what the windows share."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 log=lambda m: None):
        t_a = time.perf_counter()
        from ray_tpu.serve.llm.stream import stream_client
        self.cfg, self.model, self.seed = cfg, load_model(cfg), seed
        self.sz = self.model.sizes(cfg)
        self.requests = traffic.schedule(mix)
        self.prompts = traffic.prompt_tokens(self.requests, self.sz.vocab,
                                             seed)
        self.engine = build_engine(self.model, cfg,
                                   lambda: self.weights(seed))
        t_b = time.perf_counter()
        self.client = stream_client()
        self.open_loop = mix["kind"] == "open_loop_schedule"
        self.in_window = ([r for r in self.requests if r.due_s < seconds]
                          if self.open_loop else self.requests)
        warm_up(self.engine, self.client, self.in_window, self.sz.vocab,
                1100.0)
        log(f"set-up: engine and weights {t_b - t_a:.2f} s, warm-up "
            f"{time.perf_counter() - t_b:.2f} s")
        self.windows = 0

    def window(self, mix: dict, seconds: float, trace_dir=None,
               probe=None) -> dict:
        """Offer the mix for `seconds`, then drain. `probe(engine, share)`
        is called at the middle and the end of the window (the sweep reads
        the waiting queue there)."""
        engine = self.engine
        requests = traffic.schedule(mix)      # a sweep stretches the rate
        collector, sent = Collector(), {}
        stop = tuple(mix.get("stop_tokens", ()))
        self.windows += 1
        t0 = NOW()
        sender = threading.Thread(
            target=OFFER[mix["kind"]], name="bench-sender",
            args=(engine, self.client, collector, sent, requests,
                  self.prompts, t0, seconds, stop, f"w{self.windows}"),
            daemon=True)
        sender.start()
        traced = None
        if trace_dir:
            traced = trace_part_of_window(mix, t0, seconds, trace_dir)
        for share in (0.5, 1.0):
            remaining = t0 + share * seconds - NOW()
            if remaining > 0:
                time.sleep(remaining)
            if probe is not None:
                probe(engine, share)
        sender.join(30.0)
        drain(engine, self.client, collector, sent, self.open_loop,
              float(mix["drain_timeout_s"]))
        engine.check_health()
        w = window_metrics(collector, sent, t0, seconds)
        waits = [wt for t, wt in engine.core._queue_waits
                 if t0 <= t <= t0 + seconds]
        # a closed loop's last requests are cut off by the window, not lost
        failed = len(collector.errors) + (
            w["no_first_token"] if self.open_loop else 0)
        return {"t0": t0, "sent": len(sent), "failed": failed,
                "errors": dict(collector.errors), "traced": traced,
                "queue_wait_s": waits, **w}

    def weights(self, seed: int):
        from benchmarks.harness.weights import make_weights
        return make_weights(self.model.weight_shapes(self.sz), seed)

    def check(self, mix: dict):
        return check_against_reference(
            self.engine, self.model, self.sz, mix, self.engine.core.params,
            self.requests, self.prompts, self.seed, len(self.in_window))

    def close(self):
        self.engine.close()


def run(cell: dict, cfg: dict, mix: dict, args, t_start: float,
        log) -> dict:
    import jax
    seconds = float(args.seconds)
    compiles = CompileCounter()
    samples = {"lanes": []}
    served = Served(cfg, mix, args.seed, seconds, log)
    try:
        if args.trace:
            instrument(served.engine, samples)
        setup_s = time.perf_counter() - t_start
        compiles.start()
        w = served.window(mix, seconds,
                          args.trace_dir if args.trace else None,
                          probe=lambda e, share: (
                              compiles.stop() if share == 1.0 else None))
        # read before the check: the reference's float32 forward would set
        # the peak, and it serves no request
        mem = jax.devices()[0].memory_stats() or {}
        errors, lens = served.check(mix)
        core = served.engine.core
    finally:
        served.close()
    t0 = w["t0"]
    limit = cfg["reference"]["limit"]
    log(f"reference check: relative RMS error of logits "
        f"{[round(e, 6) for e in errors]} for prompts of {lens} tokens, "
        f"limit {limit}")
    log(f"requests sent {w['sent']}, failed {w['failed']} {w['errors']}, "
        f"tokens in window {w['tokens_in_window']}, compilations in window "
        f"{compiles.n}")
    ok = (limit is not None and all(e <= limit for e in errors)
          and w["failed"] == 0 and not np.isnan(errors).any())
    return {
        "correct": bool(ok), "attempted": w["sent"], "failed": w["failed"],
        "setup_s": setup_s, "window_s": seconds,
        "compiles_in_window": compiles.n,
        "reference_errors": errors, "reference_limit": limit,
        "memory_peak_bytes": mem.get("peak_bytes_in_use"),
        "samples": {"ttft_s": w["ttft_s"], "gap_s": w["gap_s"],
                    "lag_s": w["lag_s"], "submit_s": w["submit_s"],
                    "queue_wait_s": w["queue_wait_s"],
                    "lanes": [n for t, n in samples["lanes"]
                              if t0 <= t <= t0 + seconds],
                    "max_batch": core.max_batch,
                    "tokens_in_window": w["tokens_in_window"]},
        "traced": w["traced"],
    }


def trace_part_of_window(mix, t0, seconds, trace_dir) -> dict:
    """Trace `trace.seconds` of the window from `trace.start_s` on (both
    shrunk to fit a shorter window). The traced window is the span called
    `xplane.WINDOW` that this thread writes into the trace around its
    sleep: `xplane.traced_window` reads its length and the device's busy
    time there, and no clock of the host's is read here."""
    import jax
    from benchmarks.harness.xplane import WINDOW
    spec = mix.get("trace", {})
    length = min(float(spec.get("seconds", 5)), 0.5 * seconds)
    start = min(float(spec.get("start_s", 0)), seconds - length - 0.5)
    delay = t0 + max(start, 0.0) - NOW()
    if delay > 0:
        time.sleep(delay)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(spec.get("python_tracer", 0))
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW):
        time.sleep(length)
    jax.profiler.stop_trace()
    return {"dir": trace_dir}
