#!/usr/bin/env python
"""MLP training over sharded dense/libsvm data (bf16 MXU matmuls).

Single host::

    python examples/train_mlp.py --data train.libsvm --num-feature 28

Multi-process via the tracker (each process reads its shard)::

    dmlc-submit --cluster local --num-workers 2 -- \
        python examples/train_mlp.py --data train.libsvm --num-feature 28

Tensor parallelism: ``--model-parallel 2`` shards hidden layers over a
"model" mesh axis next to the data axis.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True)
    ap.add_argument("--num-feature", type=int, required=True)
    ap.add_argument("--hidden", default="128,128")
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--learning-rate", type=float, default=1e-3)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="mesh width of the 'model' axis for tp layers")
    ap.add_argument("--checkpoint-dir", default="",
                    help="resumable training: epoch-numbered checkpoints "
                         "(params + optimizer state); rerunning with the "
                         "same dir resumes at the latest epoch")
    args = ap.parse_args()

    import jax
    import numpy as np

    from dmlc_core_tpu import collective
    from dmlc_core_tpu.bridge.loader import MeshBatchLoader
    from dmlc_core_tpu.data.factory import create_parser
    from dmlc_core_tpu.device import init_device
    from dmlc_core_tpu.models.mlp import MLP, MLPParam
    from dmlc_core_tpu.parallel.mesh import local_shard_info, make_mesh
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    collective.init()
    init_device()   # after init(): jax.distributed must precede the backend
    part, nparts = local_shard_info()

    ndev = len(jax.devices())
    mp = max(1, args.model_parallel)
    if ndev % mp:
        raise SystemExit(f"--model-parallel {mp} does not divide {ndev} devices")
    mesh = make_mesh({"data": ndev // mp, "model": mp})

    param = MLPParam(num_feature=args.num_feature, hidden=args.hidden,
                     learning_rate=args.learning_rate)
    model = MLP(param, model_axis="model" if mp > 1 else None)
    params = model.init_params()
    opt_state = model.init_optimizer(params)

    mgr = None
    start_epoch = 0
    if args.checkpoint_dir:
        from dmlc_core_tpu.bridge.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.checkpoint_dir, keep=3)
        latest = mgr.latest_step()
        if nparts > 1:
            # rank 0 is the writer: every rank must see ITS view of the
            # store agree with rank 0's, otherwise --checkpoint-dir is not
            # shared storage and ranks would resume at different epochs
            # (desynchronized collectives deadlock). Fail loudly instead.
            agreed = int(collective.broadcast(
                np.int64(-1 if latest is None else latest), root=0))
            mine = -1 if latest is None else latest
            if agreed != mine:
                raise SystemExit(
                    f"--checkpoint-dir must be shared storage: rank "
                    f"{part} sees step {mine} but rank 0 sees {agreed}")
        if latest is not None:
            # template restore keeps the params/opt pytree structure
            params, opt_state = mgr.restore(
                latest, template=(params, opt_state))
            start_epoch = latest
            collective.tracker_print(
                f"resuming from checkpoint epoch {latest}")

    parser = create_parser(args.data, part, nparts, type="auto")
    meter = ThroughputMeter("train")
    with mesh:
        loader = MeshBatchLoader(parser, mesh, form="dense",
                                 global_batch_size=args.batch_size,
                                 num_feature=args.num_feature)
        for epoch in range(start_epoch, args.epochs):
            loss = None
            for batch in loader:
                params, opt_state, loss = model.train_step(params, opt_state,
                                                           batch)
                # static row count: padding rows carry weight 0 in the loss
                # but the meter counts staged rows without a device sync
                meter.add(0, nrows=batch.label.shape[0])
            loader.before_first()
            if loss is not None:
                collective.tracker_print(
                    f"epoch {epoch}: loss={float(loss):.5f}")
            if mgr is not None and (epoch + 1) < args.epochs:
                if part == 0:
                    mgr.save(epoch + 1, (params, opt_state))
        if mgr is not None:
            mgr.wait_until_finished()
        loader.close()
    print(meter.summary())


if __name__ == "__main__":
    main()
