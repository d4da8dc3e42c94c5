"""Set-up seconds: from the start of the process to the start of the
window (imports, data, the plan fit, warm-up and any compilation)."""


def read(run):
    return run.setup_s
