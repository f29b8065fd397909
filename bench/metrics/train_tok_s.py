"""Trained tokens of every step in the window over the window's length
(each step synced with block_until_ready)."""


def read(run):
    span = run.t_end - run.t0
    return run.steps_done * run.tokens_per_step / span if span > 0 else None
