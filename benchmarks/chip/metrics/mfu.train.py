"""Model FLOP/s utilisation of the training step: 6·N operations per
token (the benchmark's own count over the configuration's widths),
times the window's tokens per second, over the chips' bf16 peak."""


def read(run, out):
    peak = run.chips * run.peaks["bf16_flops"]
    return 100.0 * out["end_to_end"]["train_tokens_per_s"] * out["flops_per_token"] / peak
