"""Worker process for the real multi-process distributed tests (launched by
tests/test_multiprocess.py, one python process per rank — the reference's
multiple-slaves-on-one-host pattern, bin/cluster_optimizer.sh, with
jax.distributed as the CommMaster rendezvous).

Usage: python mp_worker.py <rank> <nprocs> <port> <mode> <workdir>
Prints RESULT <json> on success (rank 0's result is the one asserted)."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

rank, nprocs, port, mode, workdir = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4], sys.argv[5],
)
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=nprocs, process_id=rank
)
assert jax.process_count() == nprocs

import numpy as np  # noqa: E402

from ytklearn_tpu.parallel.mesh import make_mesh  # noqa: E402


def linear() -> dict:
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.train import HoagTrainer

    p = CommonParams()
    p.data.train_paths = [os.path.join(workdir, "train.ytk")]
    p.data.test_paths = []
    p.data.assigned = False
    p.data.unassigned_mode = "lines_avg"
    p.model.data_path = os.path.join(workdir, f"model_mp{nprocs}")
    p.loss.loss_function = "sigmoid"
    p.loss.evaluate_metric = []
    p.line_search.lbfgs_max_iter = 10
    mesh = make_mesh(len(jax.devices()))
    res = HoagTrainer(p, "linear", mesh=mesh).train()
    return {"avg_loss": float(res.avg_loss), "n_iter": int(res.n_iter)}


def gbdt() -> dict:
    from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams, ModelParams
    from ytklearn_tpu.gbdt.data import GBDTIngest
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    p = GBDTParams(
        round_num=3, max_depth=3, max_leaf_cnt=8, learning_rate=0.3,
        min_child_hessian_sum=1e-6, loss_function="sigmoid", eval_metric=[],
        approximate=[ApproximateSpec(type="sample_by_quantile", max_cnt=16)],
        model=ModelParams(
            data_path=os.path.join(workdir, f"gbdt_mp{nprocs}"), dump_freq=0
        ),
    )
    p.data.max_feature_dim = 8
    p.data.train_paths = [os.path.join(workdir, "train.ytk")]
    p.data.assigned = False
    p.data.unassigned_mode = "lines_avg"
    train, _ = GBDTIngest(p).load()
    mesh = make_mesh(len(jax.devices()))
    res = GBDTTrainer(p, mesh=mesh, engine="device").train(train=train)
    return {
        "train_loss": float(res.train_loss),
        "trees": len(res.model.trees),
        "model_text": res.model.dumps(with_stats=False),
    }


def gbst() -> dict:
    from ytklearn_tpu.boost import GBSTTrainer
    from ytklearn_tpu.config.params import CommonParams

    p = CommonParams()
    p.data.train_paths = [os.path.join(workdir, "train.ytk")]
    p.data.test_paths = []
    p.data.assigned = False
    p.data.unassigned_mode = "lines_avg"
    p.model.data_path = os.path.join(workdir, f"gbst_mp{nprocs}")
    p.loss.loss_function = "sigmoid"
    p.loss.evaluate_metric = []
    p.line_search.lbfgs_max_iter = 6
    p.k = 2
    p.tree_num = 2
    mesh = make_mesh(len(jax.devices()))
    res = GBSTTrainer(p, "gbmlr", mesh=mesh).train()
    return {"train_loss": float(res.train_loss), "trees": int(res.n_trees)}


out = {"linear": linear, "gbdt": gbdt, "gbst": gbst}[mode]()
if rank == 0:
    print("RESULT " + json.dumps(out), flush=True)
