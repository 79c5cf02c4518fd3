"""Time of one level's histogram kernel call (the Mosaic custom call)."""

from benchmarks.chip.layer_metrics import kernel_seconds

NAME = "hist_ms_per_level"
UNIT = "ms"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    def per_call(chip):
        calls = kernel_seconds(chip)
        return sum(calls) / len(calls) if calls else None

    values = [per_call(c) for c in evidence["trace"].chips]
    if any(v is None for v in values):
        return None
    return 1e3 * sum(values) / len(values)
