"""Per-device memory of the compiled step, GiB: arguments + temporaries +
outputs, less the outputs that alias donated arguments
(``memory_analysis()``)."""


def read(run):
    return run.step_bytes / 2 ** 30 if run.step_bytes else None
