"""The histogram kernel's share of its roofline: the least time the chip
could take for one round's histograms by the published peaks (per level
the larger of operations / peak FLOP/s and bytes / peak B/s, from shapes,
by ``roofline.hist_round_work``) over the kernel time measured per round.

The operations are the least the configuration's algorithm needs, not
what the kernel under test happens to do: one child of every pair below
the root is built by summation, its sibling is the parent minus it.  So
a reading near 100% means a kernel that builds half of every level below
the root at the bf16 peak of that product; one that builds every node at
that peak reads about half (32 of 63 node-products at depth 6).  At the
HIGGS and epsilon shapes every level is bound by compute in the table;
at airline's 13 features the levels that build one node are bound by
memory, by 1%."""

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
        roofline.least_seconds(flops, nbytes, evidence["device_kind"])[0]
        for flops, nbytes in roofline.hist_round_work(
            rows, config["num_feature"], config["num_bins"],
            config["max_depth"]))
    shares = []
    for chip in chips:
        rounds = rounds_traced(evidence, chip)
        mosaic = sum(kernel_seconds(chip))
        if not rounds or not mosaic:
            return None
        shares.append(least / (mosaic / rounds))
    return 100.0 * sum(shares) / len(shares)
