"""What the compiled train step needs by its `memory_analysis()`: arguments
+ outputs + temporaries - aliased, in GB."""


def read(run):
    b = run["samples"].get("step_program_bytes")
    return None if b is None else b / 1e9
