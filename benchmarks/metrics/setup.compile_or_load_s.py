"""Of the program builds' seconds, the backend's: XLA's compile, or the
persistent cache's retrieval where it held the program (`setup.cache_miss_
programs` says which a run paid). `ray_tpu_llm_program_build_s`, phase
`compile`."""
from benchmarks.harness.setup_series import BUILD_S, total


def read(run):
    return total(run, BUILD_S, phase="compile")
