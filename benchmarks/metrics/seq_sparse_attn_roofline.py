"""Share of the chip's compute peak the attention over the selection
reaches on what it has to compute: the scores and weighted sums of the
pairs the selection keeps (a query with ``c`` candidates keeps ``min(c,
topk)``: ``counts/<kind>.py: sparse_attention_forward_flops_per_step``,
from the corpus's fixed documents), forward and backward, over the peak
bf16 FLOP/s, divided by the seconds under ``df2.seq.attn_sparse`` (the
kernels' forward, its recomputation and the backward). The projections
and the indexer are in neither. Pairs of a computed tile that the
selection then drops are time and not work, so a selection that keeps a
third of the candidates cannot read over a third of what the kernels
reach. A kind with no such count, a program without the scope and a CPU
trace give nothing to read. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    count = getattr(ctx["counts"], "sparse_attention_forward_flops_per_step",
                    None)
    if trace is None or count is None or not run["steps"]:
        return None
    seconds = trace.scope_seconds.get("df2.seq.attn_sparse")
    if not seconds:
        return None
    flops = 3.0 * count(ctx["spec"]) * run["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
