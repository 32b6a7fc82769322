"""Edge-samples trained in the window over the window's seconds, on the
harness's clock; the window opens on a drained device and closes after
``block_until_ready`` on the last step's output. One sample is one
target edge of a minibatch. End-to-end."""


def read(ctx):
    run = ctx["run"]
    return run["samples"] / run["window_seconds"]
