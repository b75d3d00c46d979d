"""From the first line of the command to the opening of the window: imports,
weights, compiles or cache loads, warm-up, the reference check where it runs
before the window. Not counted: importing JAX and acquiring the chips, which
the run logs as `device_acquire_s`. Host clock."""


def read(run):
    return run["result"]["setup_s"]
