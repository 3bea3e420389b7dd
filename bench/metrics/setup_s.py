"""Set-up seconds: from the start of the process to the opening of the
window (weights, engine or train state, compiling or loading every program,
warming every shape)."""


def read(run):
    return run.setup_s
