"""The histogram kernel's share of its roofline: the least time the chip
could take for one round's histograms by the published peaks (per level
the larger of operations / peak FLOP/s and bytes / peak B/s, from shapes,
by ``roofline.hist_round_work``) over the kernel's time in a whole round.

The operations are the least the configuration's algorithm needs, not
what the kernel under test happens to do: one child of every pair below
the root is built by summation, its sibling is the parent minus it.  So
a reading near 100% means a kernel that builds half of every level below
the root at the bf16 peak of that product; one that builds every node at
that peak reads about half (32 of 63 node-products at depth 6).  At the
HIGGS and epsilon shapes every level is bound by compute in the table;
at airline's 13 features the levels that build one node are bound by
memory, by 1%.

A round here is what ``max_depth`` consecutive kernel calls build: one
tree, or ``trees_per_call`` trees where the program builds that many in
each level's call (the ``gbdt.fit.dispatch`` span's field: a softmax
round's K trees; 1 where the span carries none).  So the least time is
``trees_per_call`` times one tree's.  The kernel's time is that of the
whole rounds ``levels.traced_rounds`` finds by the kernel's names, as
``hist_ms_per_round`` reads it: the stubs of the programs the trace's
edges cut are left out, so the value does not move with where the edges
fall.  Rows are a chip's share; the time is the mean over chips.
``None``, with the reason through ``evidence["say"]``, where the span
carries no ``level_kernels`` or no whole round was traced."""

from benchmarks.chip import levels, roofline

NAME = "hist_roofline"
UNIT = "%"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    traced = levels.traced_rounds(evidence, NAME)
    if traced is None:
        return None
    names, found = traced
    trees, why = levels.trees_per_call(evidence)
    if trees is None:
        levels.sayer(evidence)(f"{NAME}: {why}")
        return None
    config = evidence["config"]
    rows = evidence["state"]["rows"] // len(found)
    least = trees * sum(
        roofline.least_seconds(flops, nbytes, evidence["device_kind"])[0]
        for flops, nbytes in roofline.hist_round_work(
            rows, config["num_feature"], config["num_bins"],
            config["max_depth"]))
    kernel_s = 1e-3 * sum(levels.kernel_ms_by_level(found, len(names)))
    return 100.0 * least / kernel_s
