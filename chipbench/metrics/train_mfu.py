"""The whole train step's share of the chips' peak: model operations per
token (``flops.train_flops_per_token``) times tokens per second in the
traced window, over chips times the bf16 peak."""
from chipbench import flops


def reduce(run):
    rate = run.steps * run.tokens_per_step / run.window_s
    per_token = flops.train_flops_per_token(run.conf["model"],
                                            int(run.traffic["seq_len"]))
    return 100.0 * per_token * rate / (run.chips * run.peaks["bf16_flops"])
