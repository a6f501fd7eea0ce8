"""Tokens of the step's input batch (W x per-worker batch x seq) times the
steps completed in the window, over the window (host clock)."""


def read(run):
    return run["tokens_per_step"] * run["steps"] / run["window_s"]
