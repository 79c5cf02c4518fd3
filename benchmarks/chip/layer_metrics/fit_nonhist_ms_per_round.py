"""Device time of one boosting round outside the histogram kernel: routing
select-sum, W build, cumsum/argmax split scoring, leaf matmul, grad/hess."""

from benchmarks.chip.layer_metrics import kernel_seconds, rounds_traced

NAME = "fit_nonhist_ms_per_round"
UNIT = "ms"
LAYER = "models: gbdt._build_tree outside the kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    def per_round(chip):
        rounds = rounds_traced(evidence, chip)
        if not rounds:
            return None
        return (chip.busy_s - sum(kernel_seconds(chip))) / rounds

    values = [per_round(c) for c in evidence["trace"].chips]
    if any(v is None for v in values):
        return None
    return 1e3 * sum(values) / len(values)
