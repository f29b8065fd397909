"""Forward and backward FLOPs per token times the window's trained
tokens per second, over the chip's bf16 peak (recompute not counted)."""
from bench import flops


def read(run):
    span = run.t_end - run.t0
    if span <= 0:
        return None
    tok_s = run.steps_done * run.tokens_per_step / span
    return 100.0 * flops.train_flops_per_token(run.dims, run.seq_len) \
        * tok_s / run.peaks["bf16_flops"]
