"""Seconds `LLMEngine.__init__` took, whole: the model object, mesh and
shardings, the engine's own weights (made and compiled or loaded as the
program `init`), the cache's pools, the jitted wrappers, the stream and
the step thread. The span `engine.setup`, read from the series the engine
writes as the span ends (`ray_tpu_llm_setup_s`, phase `engine.setup`; its
children are the other phases). Part of what the harness logs as "engine
and weights": the benchmark's own weights are made after it."""
from benchmarks.harness.setup_series import SETUP_S, total


def read(run):
    return total(run, SETUP_S, phase="engine.setup")
