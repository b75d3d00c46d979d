"""Programs the engine built that the persistent compile cache did not
hold, so that the backend compiled them: 0 in a warm run, every program in
a cold one. A run whose set-up compiled reads worse inside its own window
(PERF.md section 7); this is how it names itself. `ray_tpu_llm_program_
builds`, `cache="miss"`."""
from benchmarks.harness.setup_series import BUILDS, total


def read(run):
    return total(run, BUILDS, cache="miss")
