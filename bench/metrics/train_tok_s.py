"""Tokens of the train steps run in the window over the window, in
tokens/s.  The window ends when the last step's results are ready."""


def read(run):
    if "steps" not in run.data or "batch" not in run.data:
        return None
    return run.data["steps"] * run.data["batch"] * run.data["seq"] / run.window_s
