"""The histogram kernel's share of its roofline: the least time the chip
could take for one round's six levels by the published peaks (the larger
of operations / peak FLOP/s and bytes / peak B/s, from shapes, by
``roofline.hist_level_work``) over the kernel time measured per round.

At the HIGGS shapes every level is bound by compute in that table (the
one-hot matmul's intensity is thousands of FLOP per byte).  The kernel's
real limiter, building the one-hot in VMEM with vector compares, has no
line in a table of MXU and HBM peaks; a low share says so."""

from benchmarks.chip import roofline
from benchmarks.chip.layer_metrics import kernel_seconds, rounds_traced

NAME = "hist_roofline"
UNIT = "%"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    config, state = evidence["config"], evidence["state"]
    chips = evidence["trace"].chips
    rows = state["rows"] // len(chips)
    least = sum(
        roofline.least_seconds(
            *roofline.hist_level_work(rows, config["num_feature"],
                                      config["num_bins"], 2 ** depth),
            evidence["device_kind"])[0]
        for depth in range(config["max_depth"]))
    shares = []
    for chip in chips:
        rounds = rounds_traced(evidence, chip)
        mosaic = sum(kernel_seconds(chip))
        if not rounds or not mosaic:
            return None
        shares.append(least / (mosaic / rounds))
    return 100.0 * sum(shares) / len(shares)
