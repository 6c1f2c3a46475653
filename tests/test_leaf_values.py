"""The end-of-tree leaf lookup (gbdt/route.py::leaf_values).

The one-pass Pallas kernel must equal `leaf[pos]` BIT FOR BIT (it moves
bits and rounds nothing), the dense family must leave the round program
as it was, the choice between them is one shape rule with no environment
in it, and a training run with the kernel in its round program gives the
run the gather gives, to the last digit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_gbdt_engine import _data, _params, _rung_spec

from ytklearn_tpu.gbdt import route
from ytklearn_tpu.gbdt import trainer as trainer_mod
from ytklearn_tpu.gbdt.data import GBDTData
from ytklearn_tpu.gbdt.engine import GrowSpec
from ytklearn_tpu.gbdt.trainer import LEAF_KERNEL_MAX_NODES, GBDTTrainer


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _table(M, seed=0):
    """Leaf values of very different magnitude in one table: negative,
    both zeros, a denormal, the smallest normal, near the largest."""
    rng = np.random.RandomState(seed)
    leaf = (rng.randn(M) * np.exp(6.0 * rng.randn(M))).astype(np.float32)
    special = np.array(
        [-0.0, 0.0, 1e-42, -1.1754944e-38, 3.0e38, -3.0e38, 1e-30, -1e30],
        np.float32,
    )
    k = min(M, special.size)
    leaf[rng.permutation(M)[:k]] = special[:k]
    return leaf


def _positions(M, n, seed=1):
    """n node ids in [0, M), every id of the table among them."""
    assert n >= M
    rng = np.random.RandomState(seed)
    pos = rng.randint(0, M, size=n).astype(np.int32)
    pos[:M] = np.arange(M, dtype=np.int32)
    return rng.permutation(pos)


# -- (1) the kernel, through the interpreter, against leaf[pos] -------------


@pytest.mark.parametrize("nblk", [1, 3])
@pytest.mark.parametrize("max_nodes", [3, 509, 2045])
def test_kernel_equals_gather_bitwise(max_nodes, nblk):
    bm = 2048
    leaf = _table(max_nodes, seed=max_nodes)
    pos = _positions(max_nodes, nblk * bm, seed=nblk)
    assert set(pos.tolist()) == set(range(max_nodes))
    got = route.leaf_values(
        jnp.asarray(leaf), jnp.asarray(pos), kernels="pallas", bm=bm,
        interpret=True,
    )
    assert got.shape == (nblk * bm,) and got.dtype == jnp.float32
    want = leaf[pos]
    assert np.array_equal(_bits(got), _bits(want))
    dense = route.leaf_values(
        jnp.asarray(leaf), jnp.asarray(pos), kernels="dense", bm=bm
    )
    assert np.array_equal(_bits(dense), _bits(want))


# -- (3) the shape rule ------------------------------------------------------


@pytest.mark.parametrize(
    "kernels, max_nodes, want",
    [
        ("pallas", 509, "pallas"),  # gbdt_higgs.train: the kernel
        ("pallas", 3, "pallas"),
        ("pallas", LEAF_KERNEL_MAX_NODES, "pallas"),
        ("pallas", LEAF_KERNEL_MAX_NODES + 1, "dense"),  # the gather
        ("pallas", 1 << 20, "dense"),
        ("dense", 509, "dense"),  # CPU tests, the virtual mesh
        ("dense", 3, "dense"),
    ],
)
def test_leaf_lookup_rule(monkeypatch, kernels, max_nodes, want):
    """One property of the shape against one constant; no YTK_* variable
    has a say."""
    spec = _rung_spec(kernels=kernels, max_nodes=max_nodes)
    assert spec.leaf_lookup(LEAF_KERNEL_MAX_NODES) == want
    for name in ("YTK_LEAF_KERNEL", "YTK_LEAF_LOOKUP", "YTK_FUSED",
                 "YTK_PARTITION", "YTK_NO_PALLAS"):
        monkeypatch.setenv(name, "0" if want == "pallas" else "1")
    assert spec.leaf_lookup(LEAF_KERNEL_MAX_NODES) == want


def test_leaf_lookup_has_no_knob(tmp_path):
    from ytklearn_tpu.config import knobs

    assert not [k for k in knobs.KNOBS if "LEAF" in k]
    tr = GBDTTrainer(_params(tmp_path, "loss"), engine="device")
    spec = tr._grow_spec(28, 256)
    family = "pallas" if jax.default_backend() == "tpu" else "dense"
    assert spec.leaf_lookup(LEAF_KERNEL_MAX_NODES) == family
    # no field of the spec carries the choice: it is asked, not stored
    assert not [f for f in GrowSpec.__dataclass_fields__ if "leaf" in f]


# -- (2), (4) the round program ---------------------------------------------


def _multiclass(n=900, F=5, K=3, seed=2):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    cls = (X[:, 0] > 0.3).astype(int) + (X[:, 1] > 0.1).astype(int)
    y = np.zeros((n, K), np.float32)
    y[np.arange(n), cls] = 1.0
    return GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[str(i) for i in range(F)],
    )


def _run(tmp_path, name, params_kw, trainer_kw, train, test, policy="loss"):
    """One tiny device-engine run: the dumped model's text, the round
    program's jaxpr, and the carry it ends with (scores, loss buffers)."""
    d = tmp_path / name
    d.mkdir()
    tr = GBDTTrainer(_params(d, policy, **params_kw), engine="device", wave=4,
                     **trainer_kw)
    out = {}
    orig_probe, orig_rounds = tr._probe_compile, tr._run_rounds

    def probe_compile(jit_round, carry, data, start_round):
        key = jax.random.fold_in(jax.random.PRNGKey(20170425), start_round)
        out["jaxpr"] = str(jax.make_jaxpr(jit_round)(
            carry, jnp.asarray(start_round), key, data
        ))
        return orig_probe(jit_round, carry, data, start_round)

    def run_rounds(*a, **kw):
        out["carry"] = orig_rounds(*a, **kw)
        return out["carry"]

    tr._probe_compile = probe_compile
    tr._run_rounds = run_rounds
    out["res"] = tr.train(train=train, test=test)
    out["model"] = (d / "m.model").read_bytes()
    out["stats"] = tr.time_stats
    return out


def test_dense_family_traces_the_parent_round_program(tmp_path, monkeypatch):
    """With kernels="dense" the round program is the parent's: the lookup
    is `leaf[pos]` in line, no call, no reshape around it."""
    data, test = _data(), _data(seed=11)
    new = _run(tmp_path, "new", {"round_num": 1}, {}, data, test)

    def parent_expression(leaf, pos, **kw):
        assert kw["kernels"] == "dense"
        return leaf[pos]

    monkeypatch.setattr(trainer_mod, "leaf_values", parent_expression)
    old = _run(tmp_path, "old", {"round_num": 1}, {}, data, test)
    assert new["jaxpr"] == old["jaxpr"]
    assert "pallas_call" not in new["jaxpr"]
    assert new["stats"]["leaf_lookup_kernel"] is False


@pytest.mark.parametrize(
    "case, params_kw, trainer_kw",
    [
        ("plain", {}, {}),
        ("multiclass",
         dict(loss_function="softmax", class_num=3,
              eval_metric=["confusion_matrix"]), {}),
        ("goss", {}, dict(goss=(0.3, 0.5))),
    ],
)
def test_training_with_the_kernel_is_the_same_run(
    tmp_path, monkeypatch, case, params_kw, trainer_kw
):
    """The interpreted kernel in the round program: dumped model, loss
    buffers and final scores equal the dense family's bit for bit."""
    if case == "multiclass":
        data, test, policy = _multiclass(), _multiclass(seed=7), "level"
    else:
        data, test, policy = _data(), _data(seed=11), "loss"
    dense = _run(tmp_path, "dense", params_kw, trainer_kw, data, test, policy)

    calls = []

    def through_the_kernel(leaf, pos, *, kernels, bm, mesh=None, interpret=False):
        calls.append(pos.shape)
        return route.leaf_values(
            leaf, pos, kernels="pallas", bm=bm, mesh=mesh, interpret=True
        )

    monkeypatch.setattr(trainer_mod, "leaf_values", through_the_kernel)
    kern = _run(tmp_path, "kernel", params_kw, trainer_kw, data, test, policy)
    K = 3 if case == "multiclass" else 1
    assert len(calls) == 2 * K  # train and test rows, once a tree group
    assert "gbdt_leaf_values" in kern["jaxpr"]
    assert "gbdt_leaf_values" not in dense["jaxpr"]
    assert kern["model"] == dense["model"]
    assert len(kern["res"].model.trees) == 3 * K
    for i in (0, 1, 3, 4):  # scores, test scores, loss and test-loss buffers
        a, b = np.asarray(kern["carry"][i]), np.asarray(dense["carry"][i])
        assert a.shape == b.shape and np.array_equal(_bits(a), _bits(b)), i
    assert np.all(np.asarray(kern["carry"][3]) > 0)


# -- (5) a shard's rows under a mesh ----------------------------------------


def test_sharded_call_equals_one_device():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ytklearn_tpu.parallel.mesh import make_mesh

    mesh4 = make_mesh(n_devices=4)
    M, bm = 509, 1024
    leaf = jnp.asarray(_table(M, seed=4))
    pos_np = _positions(M, 4 * 2 * bm, seed=4)
    one = route.leaf_values(
        leaf, jnp.asarray(pos_np), kernels="pallas", bm=bm, interpret=True
    )
    pos_sh = jax.device_put(pos_np, NamedSharding(mesh4, P("data")))
    leaf_rep = jax.device_put(leaf, NamedSharding(mesh4, P()))
    sharded = jax.jit(
        lambda l, p: route.leaf_values(
            l, p, kernels="pallas", bm=bm, mesh=mesh4, interpret=True
        )
    )(leaf_rep, pos_sh)
    assert sharded.sharding.spec == P("data")
    assert len(sharded.addressable_shards) == 4
    assert np.array_equal(_bits(sharded), _bits(one))
    assert np.array_equal(_bits(one), _bits(np.asarray(leaf)[pos_np]))


# -- the chip's compiler, without the chip ----------------------------------
# The kernel at the benchmark cell's shapes, compiled for a described v5e
# (nothing runs): what the interpreter cannot refuse, Mosaic can. The one
# file of the suite that describes a topology, and only inside a fixture.
# conftest.py turns jax_enable_x64 on; no Mosaic kernel of this repo compiles
# under it (64-bit block indices), and no training run sets it: off here.


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize(
    "n, max_nodes",
    [
        (10_502_144, 509),  # gbdt_higgs.train: the train rows
        (507_904, 509),  # its test rows
        (1 << 20, LEAF_KERNEL_MAX_NODES),  # the largest table the rule allows
    ],
)
def test_kernel_compiles_for_the_chip(v5e, n, max_nodes):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(v5e.devices[0])
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda l, p: route.leaf_values(l, p, kernels="pallas", bm=16384)
        ).lower(
            jax.ShapeDtypeStruct((max_nodes,), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one),
        ).compile()
    assert "gbdt_leaf_values" in compiled.as_text()
    # one f32[n] out and nothing beside it: both reshapes are bitcasts
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_sharded_kernel_compiles_for_four_chips(v5e):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(v5e.devices), ("data",))
    n = 4 * 161 * 16384  # 10.5M rows, a bm multiple a shard
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda l, p: route.leaf_values(
                l, p, kernels="pallas", bm=16384, mesh=mesh
            )
        ).lower(
            jax.ShapeDtypeStruct((509,), jnp.float32,
                                 sharding=NamedSharding(mesh, P())),
            jax.ShapeDtypeStruct((n,), jnp.int32,
                                 sharding=NamedSharding(mesh, P("data"))),
        ).compile()
    text = compiled.as_text()
    assert "gbdt_leaf_values" in text
    # a shard looks up its own rows: no row crosses a chip
    for op in ("all-gather", "all-reduce(", "collective-permute", "all-to-all"):
        assert op not in text, op


# The kernels of the wide path (gbdt_epsilon: 409,600 rows x 2,000 columns)
# at their real shapes, for the same described chip: the full scan on packed
# words at a 64-node wave and at one node, and what makes the words.


@pytest.mark.parametrize(
    "precision, N", [("bf16", 64), ("int8", 64), ("bf16", 1)]
)
def test_wide_scan_kernel_compiles_for_the_chip(v5e, precision, N):
    from jax.sharding import SingleDeviceSharding

    from ytklearn_tpu.gbdt import hist

    one = SingleDeviceSharding(v5e.devices[0])
    F, n, bm, B = 2000, 409_600, 16384, 256

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda b4, pos, g, h, ids: hist.hist_wave(
                b4, pos, g, h, ids, B, precision=precision, kernels="pallas")
        ).lower(
            S((F, n // bm, 1, bm // 4), jnp.int32), S((n,), jnp.int32),
            S((n,), jnp.float32), S((n,), jnp.float32), S((N,), jnp.int32),
        ).compile()
    assert "gbdt_hist_scan" in compiled.as_text()


def test_wide_tiles_are_packed_within_the_byte_budget(v5e):
    """2,000 x 409,600 one-byte bins into words, 154 features at a time:
    the temporaries stay under a quarter of the 3.05 GiB the widened copy
    would take whole."""
    from jax.sharding import SingleDeviceSharding

    from ytklearn_tpu.gbdt import hist

    one = SingleDeviceSharding(v5e.devices[0])
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda b: hist.tile_bins(b, 16384, pack=True)
        ).lower(
            jax.ShapeDtypeStruct((2000, 409_600), jnp.uint8, sharding=one)
        ).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 2000 * 409_600
    assert mem.temp_size_in_bytes < (3 << 30) // 4
