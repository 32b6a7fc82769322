"""Share of the chip's compute peak a looped model's exits reach on
what they have to compute: at each pass, every token's logits against
the output rows held and its exit gate
(``counts/<kind>.py: exit_forward_flops_per_step``, from shapes),
forward and backward, over the peak bf16 FLOP/s, divided by the seconds
under ``df2.seq.exit`` (each pass's final norm, gate, head products and
per-position loss, and the exit mixture with its entropy, forward,
recomputation and backward). A kind with no such count, a program
without the scope and a CPU trace give nothing to read. Layer: kernels.
Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    count = getattr(ctx["counts"], "exit_forward_flops_per_step", None)
    if trace is None or count is None or not run["steps"]:
        return None
    seconds = trace.scope_seconds.get("df2.seq.exit")
    if not seconds:
        return None
    flops = 3.0 * count(ctx["spec"]) * run["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
