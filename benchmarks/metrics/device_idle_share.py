"""Share of the traced window in which no operation ran on the device:
1 − (union of the device's operation intervals) / window, mean over the
chips used. Layer: device. Moves ``train_samples_per_s``."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s / ctx["run"]["window_seconds"])
