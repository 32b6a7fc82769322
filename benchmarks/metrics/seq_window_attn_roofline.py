"""Share of the chip's compute peak the sliding-window attention
reaches on what it has to compute: the scores and weighted sums of the
same-document causal pairs within the window, in the sliding layers
(``counts/<kind>.py: window_attention_forward_flops_per_step``, from
the corpus's fixed length sequence), forward and backward, over the
peak bf16 FLOP/s, divided by the seconds under ``df2.seq.attn_window``
(the kernel's forward, its recomputation and its backward). The
projections, the RoPE and the gate are in neither (their scope is
``df2.seq.attn_proj``), nor are the full layers (``seq_attn_roofline``).
Pairs of a visited tile that the window or the document mask then drops
are time and not work. A kind with no such count, a program without the
scope and a CPU trace give nothing to read. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    count = getattr(ctx["counts"], "window_attention_forward_flops_per_step",
                    None)
    if trace is None or count is None or not run["steps"]:
        return None
    seconds = trace.scope_seconds.get("df2.seq.attn_window")
    if not seconds:
        return None
    flops = 3.0 * count(ctx["spec"]) * run["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
