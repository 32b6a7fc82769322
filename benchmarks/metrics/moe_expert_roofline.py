"""Share of the chip's compute peak the grouped expert products reach:
the FLOPs that the assignments to the experts held here require (the
program's own count of them, the ``training`` block's
``moe_assignments_held`` a step, times three products of hidden x expert
width, forward and backward: ``counts/lfm2_moe.py``) over the peak bf16
FLOP/s, divided by the seconds under ``df2.moe.experts``. The seconds
hold the recomputed forward and the rows of the worst-case buffer that
no expert owns; the FLOPs do not. Only a TPU trace carries scope paths.
Layer: kernels. Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    trace, run = ctx["trace"], ctx["run"]
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    if trace is None or not counted.get("moe_steps"):
        return None
    seconds = trace.scope_seconds.get("df2.moe.experts")
    if not seconds:
        return None
    per_step = counted["moe_assignments_held"] / counted["moe_steps"]
    flops = (3.0 * ctx["counts"].expert_forward_flops_per_assignment(
        ctx["spec"]) * per_step * run["steps"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
