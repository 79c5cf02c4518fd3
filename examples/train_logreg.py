#!/usr/bin/env python
"""Distributed logistic regression over sharded libsvm data.

The end-to-end slice of SURVEY.md §7: libsvm text -> sharded InputSplit ->
RowBlock -> mesh-placed batches -> SGD with data-parallel gradients.

Single host::

    python examples/train_logreg.py --data train.libsvm --num-feature 128

Multi-host via the tracker (each process reads shard process_index/process_count)::

    dmlc-submit --cluster local --num-workers 2 -- \
        python examples/train_logreg.py --data train.libsvm --num-feature 128
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True, help="libsvm URI (supports ;-lists, s3://, ?format=)")
    ap.add_argument("--num-feature", type=int, required=True)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--learning-rate", type=float, default=0.5)
    ap.add_argument("--form", choices=["dense", "sparse"], default="sparse")
    ap.add_argument("--checkpoint", default="", help="URI template, e.g. /tmp/ckpt-{version}.bin")
    args = ap.parse_args()

    from dmlc_core_tpu import collective
    from dmlc_core_tpu.bridge.loader import MeshBatchLoader
    from dmlc_core_tpu.data.factory import create_parser
    from dmlc_core_tpu.device import init_device
    from dmlc_core_tpu.models.linear import LinearModel, LinearParam
    from dmlc_core_tpu.parallel.mesh import local_shard_info, make_mesh
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    collective.init()
    init_device()   # after init(): jax.distributed must precede the backend
    part, nparts = local_shard_info()
    collective.tracker_print(f"starting logreg: {nparts} process(es)")

    parser = create_parser(args.data, part, nparts, type="auto")
    mesh = make_mesh()
    loader = MeshBatchLoader(
        parser, mesh, form=args.form,
        global_batch_size=args.batch_size,
        num_feature=args.num_feature,
        nnz_bucket=None if args.form == "dense" else args.batch_size * 64)
    model = LinearModel(LinearParam(num_feature=args.num_feature,
                                    learning_rate=args.learning_rate))
    params = model.init_params()
    start_epoch = 0
    if args.checkpoint:
        # rabit-style restart recovery: a fresh process discovers the
        # latest version on the store (collective.load_checkpoint) and
        # resumes; version N == N epochs completed
        restored = collective.load_checkpoint(args.checkpoint,
                                              template=params)
        if restored is not None:
            params = restored
            start_epoch = collective.version_number()
            collective.tracker_print(
                f"resuming from checkpoint version {start_epoch}")
    meter = ThroughputMeter("train")
    loss = None
    for epoch in range(start_epoch, args.epochs):
        if epoch:
            loader.before_first()
        for batch in loader:
            params, loss = model.train_step(params, batch)
            meter.add(0, nrows=batch.label.shape[0])
        collective.tracker_print(
            f"epoch {epoch}: loss={float(loss):.5f} ({meter.rows_per_sec:.0f} rows/s)")
        if args.checkpoint:
            collective.checkpoint(params, args.checkpoint)
    collective.tracker_print(meter.summary())
    loader.close()
    collective.finalize()


if __name__ == "__main__":
    main()
