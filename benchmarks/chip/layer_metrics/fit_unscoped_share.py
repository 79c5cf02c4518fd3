"""Share of the device time outside the Mosaic kernel that ran under no
``gbdt.*`` scope: once-per-fit work (the bin widening, row padding), ops
the compiler made itself, and any phase the program forgot to name.  100
on a program without scopes."""

from benchmarks.chip import scopes

NAME = "fit_unscoped_share"
UNIT = "%"
LAYER = "models: device time the program does not name"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    chips = scopes.scoped_ops(evidence)
    if chips is None:
        return None
    shares = []
    for ops in chips:
        total = sum(o.dur_s for o, _ in ops)
        if not total:
            return None
        shares.append(sum(o.dur_s for o, s in ops if s is None) / total)
    return 100.0 * sum(shares) / len(shares)
