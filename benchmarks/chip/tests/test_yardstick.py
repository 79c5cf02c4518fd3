"""The yardstick's own arithmetic: the trace reduction against a trace
recorded on the chip, percentiles and lateness, interval unions, the
roofline's operation counts, and each plain reference against a case
small enough to work out by hand."""

import os

import numpy as np
import pytest

from benchmarks.chip import datagen, loadgen, roofline, stats, tracereduce
from benchmarks.chip.reference import gbdt_hist, tree_walk

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


@pytest.fixture(scope="module")
def small_fit_trace():
    """A 2-round, depth-6 fit of 32,768 x 28 rows traced on a TPU v5 lite
    (PR 22's exploratory chip call)."""
    return tracereduce.from_profile(tracereduce.read_profile(
        os.path.join(TESTDATA, "smallfit.xplane.pb.gz")))


def test_trace_reduction_of_a_recorded_fit(small_fit_trace):
    trace = small_fit_trace
    assert len(trace.chips) == 1
    chip = trace.chips[0]
    mosaic = [o for o in chip.ops if o.is_mosaic]
    assert len(mosaic) == 2 * 6              # one kernel call per level
    assert all(o.opcode == "custom-call" for o in mosaic)
    assert not any(o.opcode in tracereduce.CONTAINERS for o in chip.ops)
    # one program: the device is busy nearly all the time between its first
    # and last op; the traced window also holds the host's dispatch before
    # and its wait after, so it is longer
    first, last = chip.span
    assert last - first == pytest.approx(4.13e-3, rel=0.01)
    assert trace.busy_s / (last - first) > 0.99
    assert last - first < trace.window_s == pytest.approx(6.33e-3, rel=0.01)
    kernel = sum(o.dur_s for o in mosaic)
    assert kernel == pytest.approx(2.67e-3, rel=0.01)
    top = trace.breakdown()
    assert top["device_ops"][0][0] == "tpu_custom_call:closed_call"
    assert top["device_ops"][0][1] == pytest.approx(kernel)
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    assert all(w.startswith("unattributed") for w, _ in top["idle_gaps"])


def test_fit_layer_metrics_on_the_recorded_trace(small_fit_trace):
    from benchmarks.chip.layer_metrics import (fit_nonhist_ms_per_round,
                                               hist_ms_per_level,
                                               hist_roofline,
                                               allreduce_exposed_ms_per_round)

    evidence = {"trace": small_fit_trace, "device_kind": "TPU v5 lite",
                "config": {"max_depth": 6, "num_feature": 28,
                           "num_bins": 256},
                "state": {"rows": 32768}}
    per_level = hist_ms_per_level.reduce(evidence)
    assert per_level == pytest.approx(2.67 / 12, rel=0.01)
    rest = fit_nonhist_ms_per_round.reduce(evidence)
    assert rest == pytest.approx((4.12 - 2.67) / 2, rel=0.02)
    # a program from before the kernel named its levels: no whole round
    # to read the kernel's time from, and the reader says so
    said = []
    assert hist_roofline.reduce({**evidence, "say": said.append,
                                 "spans": []}) is None
    assert said == ["hist_roofline: no gbdt.fit.dispatch span"]
    # one chip: no all-reduce in the trace, so nothing to read
    assert allreduce_exposed_ms_per_round.reduce(evidence) is None


def test_hist_roofline_on_a_recorded_trace_of_the_program_that_names_its_levels():
    """Two fits of 2 rounds, depth 6, 32,768 x 28 rows, traced on a TPU v5
    lite: one child of every pair below the root built by summation, over
    the whole rounds' kernel time (0.26782675 ms a round)."""
    from benchmarks.chip.layer_metrics import hist_roofline

    path = os.path.join(TESTDATA, "smallfit_levels.xplane.pb.gz")
    names = [f"hist_level_L{d}_n{n}" for d, n in enumerate((1, 1, 2, 4, 8,
                                                             16))]
    evidence = {"trace": tracereduce.from_profile(
                    tracereduce.read_profile(path)),
                "device_kind": "TPU v5 lite",
                "config": {"max_depth": 6, "num_feature": 28,
                           "num_bins": 256},
                "state": {"rows": 32768},
                "spans": [_dispatch(names, 1)] * 2}
    # at this small size the sibling's bytes bind some levels
    least_ms = 1e3 * sum(roofline.least_seconds(f, b, "TPU v5 lite")[0]
                         for f, b in roofline.hist_round_work(32768, 28, 256,
                                                              6))
    assert hist_roofline.reduce(evidence) == pytest.approx(
        100 * least_ms / 0.26782675, rel=1e-6)


def test_parse_op_reads_name_and_opcode_from_hlo_text():
    op = tracereduce.parse_op(
        '%closed_call.61 = f32[64,7168]{1,0:T(8,128)S(1)} custom-call('
        'bf16[64,1024]{1,0:T(8,128)(2,1)} %w), '
        'custom_call_target="tpu_custom_call"', 1e9, 5e6)
    assert (op.name, op.opcode, op.is_mosaic) == ("closed_call.61",
                                                  "custom-call", True)
    assert op.group == "tpu_custom_call:closed_call"
    assert (op.start_s, op.dur_s) == (1.0, 0.005)
    loop = tracereduce.parse_op(
        "%while.20 = (s32[]{:T(128)}, f32[8]{0:T(1024)}) while((s32[]) %t)",
        0, 1)
    assert loop.opcode == "while"
    ar = tracereduce.parse_op(
        "%all-reduce-start.3 = f32[64,13,256]{2,1,0} all-reduce-start(%p)",
        0, 1)
    assert ar.is_all_reduce and not ar.is_mosaic


def test_percentiles_latency_and_lateness():
    assert stats.percentile([], 0.5) is None
    assert stats.percentile([4.0], 0.99) == 4.0
    assert stats.percentile([1, 2, 3, 4], 0.5) == 2.5
    assert stats.percentile(list(range(101)), 0.99) == 99.0
    samples = [
        {"scheduled_s": 1.0, "sent_s": 1.002, "done_s": 1.030,
         "outcome": "ok"},
        {"scheduled_s": 2.0, "sent_s": 2.5, "done_s": 2.6,
         "outcome": "ok"},       # dispatched late: the wait counts
        {"scheduled_s": 3.0, "sent_s": 3.0, "done_s": 3.1,
         "outcome": "http_503"},  # not ok: no latency, it is a failure
    ]
    assert stats.request_latencies_ms(samples) == pytest.approx([30, 600])
    assert stats.dispatch_lateness_ms(samples) == pytest.approx(
        [2, 500, 0])


def test_interval_union_and_exposure():
    assert stats.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert stats.union_seconds([]) == 0.0
    # an all-reduce from 1 to 3 with compute from 0 to 2: 1 second exposed
    assert stats.uncovered([(1, 3)], [(0, 2)]) == pytest.approx(1.0)
    assert stats.uncovered([(1, 2)], [(0, 5)]) == 0.0


def test_roofline_counts_and_peaks():
    flops, nbytes = roofline.hist_level_work(1000, 28, 256, 32)
    assert flops == 2 * 64 * 1000 * 28 * 256
    assert nbytes == 1000 * (2 * 64 + 4 * 28) + 4 * 64 * 28 * 256
    seconds, which = roofline.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert (seconds, which) == (1.0, "compute")
    seconds, which = roofline.least_seconds(1.0, 819e9, "TPU v5 lite")
    assert (seconds, which) == (1.0, "memory")
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_round_work_builds_one_child_of_every_pair():
    assert roofline.hist_built_nodes(6) == [1, 1, 2, 4, 8, 16]
    assert roofline.hist_built_nodes(1) == [1]
    round_ = roofline.hist_round_work(1000, 28, 256, 6)
    every = [roofline.hist_level_work(1000, 28, 256, 2 ** d)
             for d in range(6)]
    assert sum(f for f, _ in round_) == pytest.approx(
        sum(f for f, _ in every) * 32 / 63)
    # the root is one plain product; a level below it also reads its
    # parents' f32 histograms and writes the siblings taken from them
    assert round_[0] == roofline.hist_level_work(1000, 28, 256, 1)
    flops, nbytes = roofline.hist_level_work(1000, 28, 256, 16)
    assert round_[5] == (flops, nbytes + 2 * (4 * 32 * 28 * 256))
    assert roofline.hist_round_work(1000, 28, 256, 1) == [round_[0]]


def _dispatch(names, trees):
    return {"name": "gbdt.fit.dispatch", "ph": "X", "ts": 0, "dur": 900,
            "args": {"level_kernels": ",".join(names),
                     "trees_per_call": trees}}


def _kernel_trace(chips, names, call_seconds, head=(), tail=()):
    """Every chip runs the same named kernel calls, 1 ms of other work
    apart: ``call_seconds`` a round's levels in order, after the calls
    ``head`` and before ``tail`` (``(level, seconds)``: the stubs of the
    programs a trace's edges cut)."""
    calls = list(head) + [(d % len(names), dur_s) for d, dur_s in
                          enumerate(call_seconds)] + list(tail)
    ops, t = [], 0.0
    for n, (level, dur_s) in enumerate(calls):
        ops.append(tracereduce.parse_op(
            f"%{names[level]}.{n} = f32[8,128] custom-call(), "
            f"{tracereduce.MOSAIC_MARK}", t * 1e9, dur_s * 1e9))
        t += dur_s + 1e-3
    return tracereduce.Trace([tracereduce.ChipTrace(c, ops, [], [])
                              for c in range(chips)])


NAMES = [f"hist_level_L{d}_n{n}" for d, n in enumerate((1, 1, 2, 4, 8, 16))]


@pytest.mark.parametrize("trees", [1, 23])
@pytest.mark.parametrize("chips,rows,num_feature", [
    (1, 11_000_000, 28), (4, 4 * 16_777_216, 13)])
def test_hist_roofline_reads_100_at_the_least_times(chips, rows,
                                                    num_feature, trees):
    """Two rounds whose six calls, each building ``trees`` trees' level,
    take exactly ``trees`` times the per-level least times of the chip's
    share of the rows (airline's one-node levels by memory, every other by
    compute): the share is 100, on one chip and on four."""
    from benchmarks.chip.layer_metrics import hist_roofline

    least = [roofline.least_seconds(f, b, "TPU v5 lite") for f, b in
             roofline.hist_round_work(rows // chips, num_feature, 256, 6)]
    assert {w for _, w in least} == (
        {"compute"} if num_feature == 28 else {"compute", "memory"})
    calls = [trees * s for s, _ in least] * 2
    evidence = {"trace": _kernel_trace(chips, NAMES, calls),
                "device_kind": "TPU v5 lite",
                "config": {"max_depth": 6, "num_feature": num_feature,
                           "num_bins": 256},
                "state": {"rows": rows},
                "spans": [_dispatch(NAMES, trees)] * 3}
    assert hist_roofline.reduce(evidence) == pytest.approx(100.0)
    # a kernel that builds every node at the same peaks reads about half
    slow = _kernel_trace(chips, NAMES, [s * 63 / 32 for s in calls])
    assert hist_roofline.reduce({**evidence, "trace": slow}) == \
        pytest.approx(100 * 32 / 63)
    # stubs at the trace's edges: the end of a round cut at the start, the
    # first levels of one cut at the end, each call cut short
    cut = _kernel_trace(chips, NAMES, calls,
                        head=[(4, 0.3 * calls[4]), (5, calls[5])],
                        tail=[(0, calls[0]), (1, 0.1 * calls[1])])
    assert hist_roofline.reduce({**evidence, "trace": cut}) == \
        pytest.approx(100.0)


def test_hist_roofline_says_why_it_reads_nothing():
    from benchmarks.chip.layer_metrics import hist_roofline

    calls = [1e-3] * 12
    evidence = {"trace": _kernel_trace(1, NAMES, calls),
                "device_kind": "TPU v5 lite",
                "config": {"max_depth": 6, "num_feature": 28,
                           "num_bins": 256},
                "state": {"rows": 1_000_000}}
    for spans, why in (
            (None, "no span buffer"),
            ([_dispatch(NAMES[:5], 1)], "has 5 levels, max_depth is 6"),
            ([_dispatch(NAMES, 1), _dispatch(NAMES, 23)],
             "carries no one trees_per_call"),
            ([_dispatch(["hist_level"] * 6, 1)], "traced no whole round")):
        said = []
        assert hist_roofline.reduce({**evidence, "spans": spans,
                                     "say": said.append}) is None
        assert len(said) == 1 and why in said[0], said
        assert said[0].startswith("hist_roofline: ")
    # a span from before trees_per_call: one tree a call
    old = {**_dispatch(NAMES, 1), "args": {"level_kernels": ",".join(NAMES)}}
    assert hist_roofline.reduce({**evidence, "spans": [old]}) == \
        pytest.approx(hist_roofline.reduce(
            {**evidence, "spans": [_dispatch(NAMES, 1)]}))


def test_reference_histogram_by_hand():
    bins = np.array([[0, 1], [1, 1], [0, 0], [1, 0]])
    node = np.array([0, 0, 1, 5])              # the last row counts nowhere
    g = np.array([1.0, 2.0, 4.0, 8.0], np.float32)
    h = np.ones(4, np.float32)
    G, H = gbdt_hist.histogram(bins, node, g, h, 2, 2)
    assert G.tolist() == [[[1, 2], [0, 3]], [[4, 0], [4, 0]]]
    assert H.tolist() == [[[1, 1], [0, 2]], [[1, 0], [1, 0]]]


def test_reference_tree_by_hand():
    """Four rows, one feature, two bins, depth 1, labels split cleanly:
    g = p - y = +-0.5, h = 0.25 at margin 0.  Splitting at bin 0 gives
    GL = 1, HL = 0.5, GR = -1, HR = 0.5: gain = 2 * 1/(0.5+1) - 0 = 4/3,
    leaves -G/(H+lam) * eta = -+(1/1.5) * 0.3 = -+0.2."""
    bins = np.array([[0], [0], [1], [1]])
    label = np.array([0, 0, 1, 1], np.float32)
    trees, margin = gbdt_hist.boost(
        bins, label, 1, max_depth=1, num_bins=2, learning_rate=0.3,
        reg_lambda=1.0, min_child_weight=0.1)
    sf, sb, leaf, default_left = trees[0]
    assert not default_left.any()
    assert (sf.tolist(), sb.tolist()) == ([0], [0])
    assert leaf == pytest.approx([-0.2, 0.2])
    assert margin == pytest.approx([-0.2, -0.2, 0.2, 0.2])
    assert gbdt_hist.logloss(margin, label) == pytest.approx(
        np.log1p(np.exp(-0.2)))
    # min_child_weight above either child's hessian sum: no split at all
    trees, _ = gbdt_hist.boost(bins, label, 1, max_depth=1, num_bins=2,
                               learning_rate=0.3, reg_lambda=1.0,
                               min_child_weight=1.0)
    assert trees[0][0].tolist() == [-1]


def test_reference_tree_walk_by_hand():
    boundaries = np.array([[0.0], [10.0]], np.float32)   # 2 features, 2 bins
    assert tree_walk.bin_rows([[-1.0, 10.0], [0.0, 9.0]],
                              boundaries).tolist() == [[0, 1], [1, 0]]
    # depth 2: the root splits on feature 0 at bin 0; its left child does
    # not split (-1: rows fall to child 2i); its right child splits on f1
    split_feat = np.array([[0, -1, 1]])
    split_bin = np.array([[0, 0, 0]])
    leaf = np.array([[1.0, 2.0, 3.0, 4.0]], np.float32)
    bins = np.array([[0, 1], [1, 0], [1, 1]])
    assert tree_walk.margins(bins, split_feat, split_bin, leaf,
                             base_score=0.5).tolist() == [1.5, 3.5, 4.5]
    p = tree_walk.predict_logistic([[5.0, 11.0]], boundaries, split_feat,
                                   split_bin, leaf)
    assert p == pytest.approx(1 / (1 + np.exp(-4.0)))


def test_generators_are_functions_of_the_seed(tmp_path):
    cfg = {"num_feature": 3, "data": {"cardinality": [0, 7, 0],
                                      "label_noise": 0.3}}
    x, y = datagen.host_rows(cfg, 5, 1000)
    x2, y2 = datagen.host_rows(cfg, 5, 1000)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert not np.array_equal(x, datagen.host_rows(cfg, 6, 1000)[0])
    assert set(np.unique(x[:, 1])) <= set(range(7))
    assert np.array_equal(loadgen.schedule(50, 2, 1), loadgen.schedule(50, 2, 1))
    at = loadgen.schedule(200, 5, 3)
    assert 800 < at.size < 1200 and at[-1] < 5 and np.all(np.diff(at) > 0)
    mix = [{"share": 0.5, "min": 1, "max": 1},
           {"share": 0.5, "min": 33, "max": 64}]
    rows = loadgen.rows_per_request(mix, 4000, 3)
    assert set(np.unique(rows)) <= {1, *range(33, 65)}
    assert 0.45 < np.mean(rows == 1) < 0.55 and rows.max() == 64


def test_libsvm_writer_matches_printf(tmp_path):
    cfg = {"num_feature": 12, "data": {"cardinality": [0] * 12,
                                       "label_noise": 0.3}}
    path = tmp_path / "t.libsvm"
    datagen.write_libsvm(str(path), cfg, 9, 300)
    x, y = datagen.host_rows(cfg, 9, 300)
    lines = path.read_text().splitlines()
    assert len(lines) == 300
    for line, xi, yi in zip(lines, x, y):
        want = f"{int(yi)} " + " ".join(f"{j}:{v:.4f}"
                                        for j, v in enumerate(xi))
        assert line.split() == want.replace("-0.0000", "0.0000").split() \
            or line.split() == want.split()


def test_device_binning_equals_searchsorted_right():
    import jax.numpy as jnp

    from dmlc_core_tpu.ops.histogram import apply_bins

    rng = np.random.default_rng(0)
    bounds = np.sort(rng.standard_normal((4, 15)).astype(np.float32), axis=1)
    x = rng.standard_normal((500, 4)).astype(np.float32)
    x[:15, 0] = bounds[0]                      # values ON a boundary go right
    got = np.asarray(datagen.bin_on_device(jnp.asarray(x).T, bounds)).T
    assert np.array_equal(got, tree_walk.bin_rows(x, bounds))
    assert np.array_equal(got, np.asarray(apply_bins(x, bounds)))
