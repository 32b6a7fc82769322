"""The whole step's share of the chip's compute peak: FLOPs the forward
and backward passes require per step (``counts/<kind>.py``, from
shapes) × steps in the window, over window seconds × chips × peak
bf16 FLOP/s. The compute-side share; these steps are memory-bound, so
it reads low. Layer: train loops. Moves ``train_samples_per_s``."""


def read(ctx):
    run = ctx["run"]
    flops = ctx["counts"].flops_per_step(ctx["spec"]) * run["steps"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (run["window_seconds"] * peak)
