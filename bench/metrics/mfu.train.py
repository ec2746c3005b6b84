"""Model FLOP utilisation of training: forward and backward operations
per token (``bench.work.model_flops_per_token``) times tokens per second
in the traced window, over the chips' bf16 peak.  Recomputation does not
count."""
from bench import work


def read(ctx):
    if not ctx["steps"]:
        return None
    flops = work.model_flops_per_token(ctx["config"],
                                       ctx["traffic"]["seq_len"])
    return 100.0 * flops * ctx["tokens_per_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
