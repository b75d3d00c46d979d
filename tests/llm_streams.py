"""Reading a generation off an in-process `LLMEngine` the way a client
does: one subscription on its push stream (shared by the engine tests)."""
import queue
import time

from ray_tpu.serve.llm.stream import stream_client


def read_stream(acc, cursor=0, timeout_s=30.0):
    """Subscribe at `cursor` to the generation that `eng.generate()`
    accepted as `acc` and read to its terminal frame. Returns (the tokens
    from `cursor` on, the terminal frame)."""
    cl = stream_client()
    sink = queue.Queue()
    assert cl.subscribe(acc["stream"], acc["rid"], acc["incarnation"],
                        acc["attempt"], cursor, sink)
    toks = []
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            msg = sink.get(timeout=max(0.01, deadline - time.monotonic()))
            assert msg.get("type") != "llm_closed", msg
            # replay and live frames may overlap: keep what is new
            toks += msg["toks"][max(0, cursor + len(toks) - msg["base"]):]
            if msg["done"]:
                return toks, msg
    finally:
        cl.unsubscribe(acc["rid"])
