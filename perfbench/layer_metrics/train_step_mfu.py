"""The train step's share of the chip's bf16 peak: FLOPs a trained token needs
(the family's ``train_flops_per_token`` with the fed documents' own causal keys) times
the non-padding tokens of a step, over the step's device time times the peak."""


def read(ctx):
    runs = ctx["trace"].module_runs(ctx["config"]["perfbench"]["programs"]["train_step"])
    fed = ctx["fed"]
    if not runs or not fed["steps"]:
        return None
    step_s = sum(e - s for s, e in runs) / len(runs)
    tokens_per_step = fed["tokens"] / fed["steps"]
    per_token = ctx["family"].train_flops_per_token(ctx["config"], fed["mean_keys"])
    return 100.0 * per_token * tokens_per_step / (step_s * ctx["peaks"]["bf16_flops_per_s"])
