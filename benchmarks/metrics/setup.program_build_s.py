"""Wall seconds of the calls that built a program, over all the engine's
programs (`init`, `_step`, `_next`, `_place`, a `_pre` a prefill bucket):
from JAX's first sight of the function to the return of the call that
dispatched it. The share of set-up that is the program's and not the
device's: warm-up less this is requests running. `ray_tpu_llm_program_
build_s`, every program and phase (`trace`, `lower`, `compile`, `rest`)."""
from benchmarks.harness.setup_series import BUILD_S, total


def read(run):
    return total(run, BUILD_S)
