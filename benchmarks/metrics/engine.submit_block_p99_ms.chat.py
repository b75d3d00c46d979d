"""How long `LLMEngine.generate()` kept the sender: it waits for the engine
lock, which the step thread holds through each step. 99th percentile."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("submit_s", []), 99)
    return None if p is None else p * 1e3
