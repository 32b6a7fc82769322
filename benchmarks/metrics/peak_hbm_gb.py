"""What the fullest chip really holds as the window closes, before the
reference touches the device: the larger of the allocator's
``peak_bytes_in_use`` and ``bytes_in_use + bytes_reserved``. The first
alone leaves out what a loaded program holds reserved for its
temporaries (the compiler's ``temp_size``, held from the first step
on), which is nearly all of a train step's memory (PERF.md section 6).
``run.py`` reports the same number as ``memory_peak_bytes``. Layer:
device. Moves ``train_samples_per_s`` (room for a larger batch or
fleet). The CPU backend keeps no memory statistics."""

chip_only = True


def read(ctx):
    peak = ctx["run"].get("memory_peak_bytes")
    return None if not peak else peak / 1e9
