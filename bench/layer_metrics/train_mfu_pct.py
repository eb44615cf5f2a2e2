"""Model FLOP/s utilisation of the train step: model FLOPs per token (the
configuration's reference module's `train_flops_per_token`, no
recomputation) times the traced window's tokens per second, over the
chips' bf16 peak."""


def read(run: dict):
    if run.get("driver") != "train":
        return None
    return 100.0 * run["tokens_per_s"] * run["flops_per_token"] / (
        run["chips"] * run["peak"]["flops_per_s"])
