"""Operations and bytes of one step of the stand-in kind ``wide_mlp``,
from shapes."""


def flops_per_step(spec: dict) -> int:
    """Forward and backward (weight and input gradients) of ``layers``
    square products and the head's, on ``batch`` rows."""
    m = spec["model"]
    return 3 * 2 * spec["batch"] * (m["layers"] * m["width"] ** 2
                                    + m["width"])


def gather_bytes_per_step(spec: dict) -> int:
    """The batch's float32 feature rows and labels, read by row id."""
    return spec["batch"] * (spec["model"]["width"] + 1) * 4
