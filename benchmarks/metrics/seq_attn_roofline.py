"""Share of the chip's compute peak the sequence attention reaches on
what it has to compute: the scores and weighted sums of the
same-document causal pairs (``counts/lfm2_moe.py``, from the corpus's
fixed length sequence), forward and backward, over the peak bf16
FLOP/s, divided by the seconds under ``df2.seq.attn``. The projections
are in neither (their scope is ``df2.seq.attn_proj``). Pairs that the
document mask then drops are time and not work: packed documents far
shorter than a sequence read low here. Only a TPU trace carries scope
paths. Layer: kernels. Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, run = ctx["trace"], ctx["run"]
    if trace is None or not run["steps"]:
        return None
    seconds = trace.scope_seconds.get("df2.seq.attn")
    if not seconds:
        return None
    flops = (3.0 * ctx["counts"].attention_forward_flops_per_step(ctx["spec"])
             * run["steps"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (
        seconds * run["chips"])
