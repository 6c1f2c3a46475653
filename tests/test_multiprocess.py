"""REAL multi-process distributed training: N python processes, each with
its own CPU device, joined through jax.distributed + Gloo collectives —
the live equivalent of the reference's multiple-LocalTrainWorkers-against-
one-CommMaster test pattern (SURVEY §4.5). Each rank ingests its lines_avg
shard; global arrays are assembled from per-process shards; the final model
must match single-process training on the full data."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mp_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_data(tmp_path, n=240):
    rng = np.random.RandomState(5)
    lines = []
    for i in range(n):
        x = rng.randn(4)
        y = int(x[0] * 1.2 - x[1] + 0.2 * rng.randn() > 0)
        feats = ",".join(f"f{j}:{x[j]:.5f}" for j in range(4))
        lines.append(f"1###{y}###{feats}")
    (tmp_path / "train.ytk").write_text("\n".join(lines) + "\n")


def _run(mode, tmp_path, nprocs):
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # one real CPU device per process
    # stderr goes to files, not pipes: a rank blocking on a full stderr pipe
    # while its peer sits in a collective would deadlock the whole group
    procs = []
    errf = []
    for r in range(nprocs):
        ef = open(tmp_path / f"rank{r}.{mode}.{nprocs}.err", "w+")
        errf.append(ef)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(r), str(nprocs), str(port), mode,
             str(tmp_path)],
            stdout=subprocess.PIPE, stderr=ef, env=env, text=True,
        ))
    outs = []
    try:
        for p, ef in zip(procs, errf):
            out, _ = p.communicate(timeout=420)
            ef.seek(0)
            outs.append((p.returncode, out, ef.read()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for ef in errf:
            ef.close()
    for rc, out, err in outs:
        if rc != 0 and "Multiprocess computations aren't implemented" in err:
            # this jaxlib build has no cross-process CPU collectives — the
            # capability under test does not exist in the environment
            pytest.skip("jaxlib lacks multiprocess CPU collectives")
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-3000:]}"
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line: {outs}")


def test_two_process_linear_matches_single(tmp_path):
    _write_data(tmp_path)
    dist = _run("linear", tmp_path, 2)
    single = _run("linear", tmp_path, 1)
    # same global rows, same optimizer -> same trajectory up to reduction
    # order; the loss must agree tightly
    assert dist["avg_loss"] == pytest.approx(single["avg_loss"], rel=1e-3)
    assert dist["avg_loss"] < 0.45


@pytest.mark.skipif(
    not os.path.exists(os.environ.get("YTK_REF", "/root/reference")),
    reason="reference demo conf not present",
)
def test_cluster_launcher_two_ranks(tmp_path):
    """bin/cluster_optimizer.sh forks N CLI ranks against one coordinator
    (reference: bin/cluster_optimizer.sh slave fan-out)."""
    _write_data(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["YTK_COORDINATOR_PORT"] = str(_free_port())
    env["YTK_MASTER_LOG"] = str(tmp_path / "master.log")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        ["bash", os.path.join(REPO, "bin", "cluster_optimizer.sh"), "linear",
         f"{os.environ.get('YTK_REF', '/root/reference')}/demo/linear/binary_classification/linear.conf",
         "2",
         "--set", f"data.train.data_path={tmp_path / 'train.ytk'}",
         "--set", "data.test.data_path=",
         "--set", f"model.data_path={tmp_path / 'model'}",
         "--set", "optimization.line_search.lbfgs.convergence.max_iter=6"],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_iter"] == 6 and res["avg_loss"] < 0.45
    assert (tmp_path / "model").exists()

    # master-log aggregation (reference: utils/LogUtils.java:33-65 — every
    # worker's log lands in ONE master log): both ranks' lines appear,
    # rank-labeled, in the configured file
    master = (tmp_path / "master.log").read_text()
    assert "[rank 0]" in master, master[:2000]
    assert "[rank 1]" in master, master[:2000]
    # training metric lines are grep-able, per the running_guide recipe
    assert "train" in master and "loss" in master


def test_two_process_gbst_matches_single(tmp_path):
    _write_data(tmp_path)
    dist = _run("gbst", tmp_path, 2)
    single = _run("gbst", tmp_path, 1)
    assert dist["trees"] == single["trees"] == 2
    assert dist["train_loss"] == pytest.approx(single["train_loss"], rel=1e-3)


def test_two_process_gbdt_matches_single(tmp_path):
    _write_data(tmp_path)
    dist = _run("gbdt", tmp_path, 2)
    single = _run("gbdt", tmp_path, 1)
    assert dist["trees"] == single["trees"] == 3
    # bin boundaries come from a cross-process candidate merge that is
    # approximate by design (reference: GK-summary allreduce), so trees may
    # differ slightly — quality must land in the same band
    assert dist["train_loss"] == pytest.approx(single["train_loss"], rel=0.05)
    # the distributed model is a valid, reloadable text model
    from ytklearn_tpu.gbdt.tree import GBDTModel

    m = GBDTModel.loads(dist["model_text"])
    assert len(m.trees) == 3
