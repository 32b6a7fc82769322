"""Share of the chip's compute peak the indexer's scores reach on what
they have to compute: one product of the indexer's head size for every
same-document causal pair, indexer head and layer
(``counts/<kind>.py: index_forward_flops_per_step``, from the corpus's
fixed documents), forward only (the selection is piecewise constant:
there is no backward pass), over the peak bf16 FLOP/s, divided by the
seconds under ``df2.seq.index`` (the products, the ReLU and the head
sum). The indexer's projections are in neither (their scope is
``df2.seq.attn_proj``), nor is the ranking (``seq_select_ms``). Pairs a
panel scores beyond a query's candidates are time and not work. A kind
with no such count, a program without the scope and a CPU trace give
nothing to read. Layer: kernels. Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    count = getattr(ctx["counts"], "index_forward_flops_per_step", None)
    if trace is None or count is None or not run["steps"]:
        return None
    seconds = trace.scope_seconds.get("df2.seq.index")
    if not seconds:
        return None
    flops = count(ctx["spec"]) * run["steps"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
