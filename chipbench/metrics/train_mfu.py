"""Whole-step share of the chips' bf16 peak, in %: tokens/s over the window
times the FLOPs a token requires (``flops_per_token`` of the yardstick the
configuration names), over the peak of every chip used."""

from chipbench import spec


def read(run):
    rate = run.e2e.get("train_tokens_per_s")
    if not rate:
        return None
    flops = spec.load_yardstick(run.config["reference"]).flops_per_token(run.config["model"])
    return 100.0 * rate * flops / (run.peaks["bf16_flops_per_s"] * run.cell["chips"])
