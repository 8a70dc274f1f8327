"""Model (models/transformer.py): model FLOP/s utilization — the
benchmark's own FLOPs per trained token (forward x 3, attention counted as
attended, remat not counted) x the measured tokens per second, over chips
x peak bf16 FLOP/s. An end-to-end utilization, not a kernel's roofline."""

from benchmark.lib import flops


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s")
    if rate is None or ctx["peaks"] is None:
        return None
    per_token = flops.train_flops_per_token(ctx["c"], ctx["mix"]["seq_len"])
    return 100.0 * per_token * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["flops_bf16"])
