"""Model FLOP utilisation of the whole training step: model operations
per token (``chipbench.flops``) times the window's tokens per second,
over the chips' bf16 peak, in percent. Recomputed operations do not
count."""


def read(run):
    tokens = run.units.get("tokens")
    per_token = run.extras.get("flops_per_token")
    if not tokens or not per_token or not run.peaks:
        return None
    rate = tokens / run.window_s
    return per_token * rate / (run.chips * run.peaks["bf16_flops_per_s"]) \
        * 100.0
