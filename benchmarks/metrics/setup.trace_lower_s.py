"""Of the program builds' seconds, JAX's trace of the function and its
lowering to an MLIR module (where a `pallas_call` is lowered to Mosaic,
once a layer): what every process pays whatever the compile cache holds.
`ray_tpu_llm_program_build_s`, phases `trace` and `lower`."""
from benchmarks.harness.setup_series import BUILD_S, total


def read(run):
    parts = [total(run, BUILD_S, phase=p) for p in ("trace", "lower")]
    return None if None in parts else sum(parts)
