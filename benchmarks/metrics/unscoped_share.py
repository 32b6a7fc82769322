"""Share of the device's busy time that lies under none of the
program's ``df2.*`` scopes: what the compiler makes itself (layout
copies, the loops a reshape is turned into) and whatever the program
stops naming. It guards the metrics that read a scope by its name: a
scope renamed or dropped shows here as a jump, where those only fall
silent. Only a TPU trace carries scope paths. Layer: train loops. Moves
``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.unscoped_s is None:
        return None
    busy = trace.scoped_s + trace.unscoped_s
    return 100.0 * trace.unscoped_s / busy if busy > 0 else None
