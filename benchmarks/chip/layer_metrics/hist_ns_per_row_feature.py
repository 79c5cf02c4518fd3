"""What one row-feature costs in the histogram kernel: the mean Mosaic
call's time (``hist_ms_per_level``) over the rows one chip holds times the
features.

A level's call builds one one-hot per row and feature whatever the node
count, so this is the kernel's unit cost, comparable across cells: 28 and
13 features in one accumulator block on HIGGS and airline, 2,000 features
in 16 blocks on epsilon."""

from benchmarks.chip.layer_metrics import hist_ms_per_level

NAME = "hist_ns_per_row_feature"
UNIT = "ns"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    per_call_ms = hist_ms_per_level.reduce(evidence)
    if per_call_ms is None:
        return None
    rows = evidence["state"]["rows"] // len(evidence["trace"].chips)
    return 1e6 * per_call_ms / (rows * evidence["config"]["num_feature"])
