#!/usr/bin/env python
"""Hist-GBDT training (the XGBoost-hist workload) over the data pipeline.

Reads csv or libsvm (dense features), quantile-bins on a sample, trains
boosted trees in a single compiled program, reports accuracy and rows/sec::

    python examples/train_gbdt.py --data 'higgs.csv?format=csv&label_column=0' \
        --num-feature 28 --rounds 50 --max-depth 6
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fit_resumable(model, param, bins, y, args):
    """Round-by-round fit with CheckpointManager: rerunning with the same
    --checkpoint-dir resumes at the latest step (docs/guide.md recipe)."""
    import time

    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager
    from dmlc_core_tpu.models.gbdt import TreeEnsemble

    mgr = CheckpointManager(args.checkpoint_dir, keep=3)
    latest = mgr.latest_step()
    B = len(y)
    mshape = (B, param.num_class) if param.objective == "softmax" else (B,)
    if latest is None:
        start, trees = 0, []
        margin = np.full(mshape, param.base_score, np.float32)
    else:
        state = {k[2:-2]: v for k, v in mgr.restore(latest).items()}
        start = int(state["round"])
        margin = np.asarray(state["margin"], np.float32)
        trees = []
        for i in range(start):
            arity = len([k for k in state if k.startswith(f"t{i}_")])
            trees.append(tuple(np.asarray(state[f"t{i}_{j}"])
                               for j in range(arity)))
        print(f"resuming from checkpoint step {latest} "
              f"({start}/{args.rounds} rounds done)")

    gmargin = jnp.asarray(margin)
    weight = jnp.ones((B,), jnp.float32)
    label = jnp.asarray(y)
    t0 = time.perf_counter()
    for r in range(start, args.rounds):
        gmargin, tree = model.boost_round(gmargin, bins, label, weight,
                                          round_index=r)
        trees.append(tuple(np.asarray(a) for a in tree))
        if (r + 1) % args.checkpoint_every == 0 and (r + 1) < args.rounds:
            payload = {"round": np.int64(r + 1),
                       "margin": np.asarray(gmargin)}
            for i, t in enumerate(trees):
                for j, arr in enumerate(t):
                    payload[f"t{i}_{j}"] = arr
            mgr.save(r + 1, payload)
    jax.block_until_ready(gmargin)
    mgr.wait_until_finished()
    secs = time.perf_counter() - t0
    ensemble = TreeEnsemble(*[np.stack([t[i] for t in trees])
                              for i in range(6)])
    # report only the rounds THIS run trained: secs covers those alone, so
    # a resumed run must not claim the skipped rounds' throughput
    return ensemble, np.asarray(gmargin), secs, args.rounds - start


def _fit_distributed(model, bins, y, collective):
    """One GLOBAL data-parallel fit over every device of the worker world
    (the tests/test_distributed_gbdt.py path as a user-facing CLI; one
    process driving a multi-chip host takes it too): rows are sharded
    over a global mesh, histogram aggregation compiles to collectives,
    and every rank holds the SAME ensemble.

    Ranks' shard sizes differ by up to a row after InputSplit partitioning,
    so every rank pads to the max local count with weight-0 rows — inert in
    the histogram (zero grad/hess mass).  Returns (ensemble, acc, secs,
    global_rows).
    """
    import time

    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    n_local = len(y)
    n_max = int(collective.allreduce(np.asarray([n_local]), op="max")[0])
    # the global dim (n_max * world) must shard evenly over ALL devices
    # (world * local_device_count), so round the per-rank count up to a
    # multiple of the local device count (multi-chip hosts: 4 devices/host)
    ldc = jax.local_device_count()
    n_max = -(-n_max // ldc) * ldc
    pad = n_max - n_local
    F = bins.shape[1]
    if pad:
        bins = np.concatenate([bins, np.zeros((pad, F), bins.dtype)])
        y = np.concatenate([y, np.zeros(pad, y.dtype)])
    w = np.ones(n_max, np.float32)
    if pad:
        w[n_local:] = 0.0
    world = collective.get_world_size()
    B = n_max * world
    mesh = make_mesh()
    sh2 = data_sharding(mesh, ndim=2)
    sh1 = data_sharding(mesh, ndim=1)
    gbins = jax.make_array_from_process_local_data(sh2, bins, (B, F))
    glabel = jax.make_array_from_process_local_data(
        sh1, np.asarray(y, np.float32), (B,))
    gw = jax.make_array_from_process_local_data(sh1, w, (B,))
    with mesh:
        ens, margin = model.fit_binned(gbins, glabel, weight=gw)  # warm
        jax.block_until_ready(margin)
        t0 = time.perf_counter()
        ens, margin = model.fit_binned(gbins, glabel, weight=gw)
        jax.block_until_ready(margin)
        secs = time.perf_counter() - t0
        if model.param.objective == "softmax":
            hit = (jnp.argmax(margin, axis=1) == glabel)
        else:
            hit = ((margin > 0) == glabel)
        total_w = jnp.sum(gw)          # == global REAL row count (pads are 0)
        acc = float(jnp.sum(hit * gw) / total_w)
        global_rows = int(round(float(total_w)))
        # materialize the (small) ensemble on every host: an explicit
        # replicated out-sharding inserts the all-gather
        from dmlc_core_tpu.parallel.mesh import replicated_sharding

        rep = jax.jit(lambda a: a, out_shardings=replicated_sharding(mesh))
        ens = jax.tree_util.tree_map(lambda a: np.asarray(rep(a)), ens)
    return ens, acc, secs, global_rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True)
    ap.add_argument("--num-feature", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--max-depth", type=int, default=6)
    ap.add_argument("--num-bins", type=int, default=256)
    ap.add_argument("--learning-rate", type=float, default=0.3)
    ap.add_argument("--hist-method", default="auto",
                    choices=["auto", "pallas", "scatter"],
                    help="histogram algorithm (auto: pallas VMEM kernel on "
                         "a TPU, scatter everywhere else)")
    ap.add_argument("--objective", default="logistic",
                    choices=["logistic", "squared", "softmax"])
    ap.add_argument("--num-class", type=int, default=1,
                    help="classes for --objective softmax")
    ap.add_argument("--min-split-loss", type=float, default=0.0,
                    help="gamma: minimum gain to split")
    ap.add_argument("--reg-alpha", type=float, default=0.0,
                    help="L1 on leaf weights")
    ap.add_argument("--monotone-constraints", default="",
                    help="per-feature directions, e.g. '(1,0,-1)'")
    ap.add_argument("--scale-pos-weight", type=float, default=1.0,
                    help="positive-class weight multiplier (logistic)")
    ap.add_argument("--subsample", type=float, default=1.0)
    ap.add_argument("--colsample-bytree", type=float, default=1.0)
    ap.add_argument("--colsample-bylevel", type=float, default=1.0)
    ap.add_argument("--colsample-bynode", type=float, default=1.0)
    ap.add_argument("--max-delta-step", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--handle-missing", action="store_true",
                    help="sparsity-aware splits: absent/NaN features take "
                         "a reserved bin with learned default directions")
    ap.add_argument("--eval-data", default="",
                    help="held-out URI: track per-round eval loss "
                         "(logloss/mlogloss/MSE per objective)")
    ap.add_argument("--early-stopping-rounds", type=int, default=0,
                    help="stop when eval loss hasn't improved for N rounds "
                         "(needs --eval-data); ensemble truncates to the "
                         "best round")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--checkpoint-dir", default="",
                    help="resumable training: step-numbered checkpoints "
                         "land here every --checkpoint-every rounds; "
                         "rerunning with the same dir resumes from the "
                         "latest one (docs/guide.md 'Crash recovery')")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    args = ap.parse_args()

    import jax

    from dmlc_core_tpu.bridge.batching import dense_batches
    from dmlc_core_tpu.bridge.checkpoint import save_checkpoint
    from dmlc_core_tpu.data.factory import create_parser
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.parallel.mesh import local_shard_info
    from dmlc_core_tpu.utils.profiler import ThroughputMeter, device_timer

    # bring up the collective BEFORE sharding: under a tracker launch,
    # jax.process_count() reflects the worker world only after
    # collective.init() has initialized jax.distributed
    from dmlc_core_tpu import collective
    from dmlc_core_tpu.device import init_device

    collective.init()
    init_device()   # after init(): jax.distributed must precede the backend
    part, nparts = local_shard_info()
    parser = create_parser(args.data, part, nparts, type="auto")

    # materialize this shard densely (hist-GBDT trains on the binned matrix)
    fill = np.nan if args.handle_missing else 0.0

    def load_dense(p, meter=None):
        xs, ys = [], []
        for batch in dense_batches(p, 8192, args.num_feature,
                                   fill_value=fill):
            n = batch.num_rows
            xs.append(batch.x[:n])
            ys.append(batch.label[:n])
            if meter is not None:
                meter.add(p.bytes_read(), nrows=n)
        return np.concatenate(xs), np.concatenate(ys)

    meter = ThroughputMeter("ingest")
    x, y = load_dense(parser, meter)
    print(meter.summary())

    param = GBDTParam(num_boost_round=args.rounds, max_depth=args.max_depth,
                      num_bins=args.num_bins, learning_rate=args.learning_rate,
                      hist_method=args.hist_method,
                      min_split_loss=args.min_split_loss,
                      reg_alpha=args.reg_alpha,
                      monotone_constraints=args.monotone_constraints,
                      scale_pos_weight=args.scale_pos_weight,
                      subsample=args.subsample,
                      colsample_bytree=args.colsample_bytree,
                      colsample_bylevel=args.colsample_bylevel,
                      colsample_bynode=args.colsample_bynode,
                      max_delta_step=args.max_delta_step, seed=args.seed,
                      objective=args.objective, num_class=args.num_class,
                      handle_missing=args.handle_missing)
    model = GBDT(param, num_feature=args.num_feature)
    # under a multi-worker launch, merge per-shard quantile summaries so all
    # ranks bin identically (the XGBoost distributed-sketch step)
    comm = collective if nparts > 1 else None
    # count=len(x): the sample may be capped but the merge must weight this
    # shard by its true size
    model.make_bins(x[: min(len(x), 100_000)], comm=comm, count=len(x))
    bins = np.asarray(model.bin_features(x)).astype(np.int32)

    rounds_run = args.rounds
    ndev = jax.device_count()
    single_device_flow = bool(args.eval_data or args.checkpoint_dir)
    # eval/resume flows are single-device features for now.  Across workers
    # that is an error — never silently train per-shard models; on one
    # multi-chip host they run, and say what they left idle
    if nparts > 1 and single_device_flow:
        ap.error("--eval-data/--checkpoint-dir are single-host flows; "
                 "under a multi-worker launch the fit is one global "
                 "data-parallel program")
    if ndev > 1 and single_device_flow:
        print(f"note: --eval-data/--checkpoint-dir train on one device; "
              f"{ndev - 1} of {ndev} devices stay idle")
    elif ndev > 1:
        # one GLOBAL model over every device: the worker world's, or a
        # single process's own chips
        ensemble, acc, secs, global_rows = _fit_distributed(
            model, bins, y, collective)
        rows_per_sec = global_rows * rounds_run / secs
        print(f"trained {rounds_run} rounds on {global_rows} rows over "
              f"{nparts} workers ({ndev} devices) in {secs:.2f}s "
              f"({rows_per_sec:,.0f} rows/sec), train acc {acc:.4f}")
        if args.checkpoint and part == 0:
            save_checkpoint(args.checkpoint, ensemble._asdict())
            print(f"checkpoint written to {args.checkpoint}")
        collective.finalize()
        return
    if args.checkpoint_dir:
        if args.eval_data or args.early_stopping_rounds:
            ap.error("--checkpoint-dir cannot be combined with --eval-data/"
                     "--early-stopping-rounds (the resumable loop does not "
                     "track eval curves yet)")
        ensemble, margin, secs, rounds_run = _fit_resumable(
            model, param, bins, y, args)
    elif args.eval_data:
        ex, ev_y = load_dense(create_parser(args.eval_data, 0, 1,
                                            type="auto"))
        ev_bins = np.asarray(model.bin_features(ex)).astype(np.int32)
        # fit_with_eval compiles to one jit by default: warm up once so
        # the reported seconds are train time, not compile time
        (ensemble, history), secs = device_timer(
            lambda b, yy: model.fit_with_eval(
                b, yy, ev_bins, ev_y,
                early_stopping_rounds=args.early_stopping_rounds),
            bins, y)
        rounds_run = len(history)
        print(f"eval: first {history[0]['eval_loss']:.5f} -> "
              f"last {history[-1]['eval_loss']:.5f} "
              f"({ensemble.num_trees} trees kept)")
        margin = model.predict_margin(ensemble, bins)
    else:
        (ensemble, margin), secs = device_timer(
            lambda b, yy: model.fit_binned(b, yy), bins, y)
    if args.objective == "softmax":
        acc = float((np.asarray(margin).argmax(1) == y).mean())
    else:
        acc = float(((np.asarray(margin) > 0) == y).mean())
    rows_per_sec = len(y) * rounds_run / secs
    print(f"trained {rounds_run} rounds on {len(y)} rows in {secs:.2f}s "
          f"({rows_per_sec:,.0f} rows/sec/chip), train acc {acc:.4f}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, ensemble._asdict())
        print(f"checkpoint written to {args.checkpoint}")
    collective.finalize()


if __name__ == "__main__":
    main()
