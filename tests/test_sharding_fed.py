"""Sharded == unsharded parity for the population-scale device-mesh engine.

Every driver accepts `config.mesh` (a ("clusters", "clients") federation
mesh, `launch.mesh.make_federation_mesh`); `sharding.fed.shard_plan` rewrites
the driver's ScanPlan so the compiled chunk runs under shard_map with the
client/cluster axes mapped to devices.  The contract (sharding/fed.py module
docstring): params, eval metrics and ledger aggregates BIT-identical to the
single-device run; loss log scalars bit-identical in grad mode, within 1 ulp
in delta modes.

The XLA device count locks at backend init, so the multi-device cells are
guarded by `jax.device_count() >= 8` and a meta-test re-invokes pytest on
this file in a subprocess with --xla_force_host_platform_device_count=8.
Under the CI sharding-smoke job (XLA_FLAGS exported) the cells run directly
and the meta-test skips.

Bit-exactness regime: XLA:CPU's batched GEMM is per-lane width-DEPENDENT for
large layers under forced host devices (fed.py docstring), so the bit-exact
end-to-end cells use a tiny 16->32->4 model whose GEMMs sit in the
width-invariant regime; an MNIST-MLP cell pins params at tight allclose plus
exact ledger aggregates instead.
"""
import dataclasses
import functools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import FedCHSConfig, run_fed_chs
from repro.core.baselines import (
    FedAvgConfig,
    HierLocalQSGDConfig,
    WRWGDConfig,
    run_fedavg,
    run_hier_local_qsgd,
    run_wrwgd,
)
from repro.core.sweep import run_sweep
from repro.launch.mesh import make_federation_mesh
from repro.sharding.fed import FED_AXES, resolve_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (runs via test_forced_8_devices_subprocess)")


def _mesh():
    m = make_federation_mesh(2, 4)
    assert m.size == 8 and m.axis_names == FED_AXES
    return m


@functools.lru_cache(maxsize=None)
def tiny_task(ragged: bool = False):
    """Tiny task whose GEMMs sit in XLA:CPU's width-invariant regime, so the
    sharded parity checks are BIT-exact end to end (see module docstring)."""
    from repro.core.simulation import FLTask
    from repro.data import assign_clusters, dirichlet_partition
    from repro.data.synthetic import Dataset, DatasetSpec
    from repro.models.classifier import Classifier, _dense_init

    spec = DatasetSpec("tiny", (4, 4, 1), 4, 400, 80)
    rng = np.random.default_rng(0)
    train_y = rng.integers(0, 4, 400).astype(np.int32)
    test_y = rng.integers(0, 4, 80).astype(np.int32)
    protos = rng.normal(size=(4, 4, 4, 1)).astype(np.float32)
    train_x = (protos[train_y]
               + 0.3 * rng.normal(size=(400, 4, 4, 1))).astype(np.float32)
    test_x = (protos[test_y]
              + 0.3 * rng.normal(size=(80, 4, 4, 1))).astype(np.float32)
    ds = Dataset(spec, train_x, train_y, test_x, test_y)

    def init(key):
        k1, k2 = jax.random.split(key)
        return {"fc1": _dense_init(k1, 16, 32), "out": _dense_init(k2, 32, 4)}

    def apply(p, x):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
        return x @ p["out"]["w"] + p["out"]["b"]

    model = Classifier("tiny-mlp", init, apply, 4)
    clients = dirichlet_partition(train_y, 20, 0.6, seed=0)
    if ragged:  # 7/5/4/4: exercises padded client slots on every shard
        clusters = [list(range(0, 7)), list(range(7, 12)),
                    list(range(12, 16)), list(range(16, 20))]
    else:
        clusters = assign_clusters(20, 4, seed=0)
    return FLTask(model, ds, clients, clusters, batch_size=8, seed=0)


def _check(r0, r1, exact_loss=False):
    """The fidelity contract: params/metrics/ledger bit-identical; loss log
    scalars exact in grad mode, within 1 ulp (rtol 1e-6) in delta modes."""
    for a, b in zip(jax.tree.leaves(r0.final_params),
                    jax.tree.leaves(r1.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert r0.test_acc == r1.test_acc
    if exact_loss:
        assert r0.train_loss == r1.train_loss
    else:
        np.testing.assert_allclose(r0.train_loss, r1.train_loss,
                                   rtol=1e-6, atol=0)
    assert r0.ledger.total_bits() == r1.ledger.total_bits()
    assert r0.ledger.history == r1.ledger.history


def _run_pair(run, task, cfg, exact_loss=False):
    r0 = run(task, cfg)
    r1 = run(task, dataclasses.replace(cfg, mesh=_mesh()))
    _check(r0, r1, exact_loss=exact_loss)


# --------------------------------------------------------------------------
# bit-exact parity cells: 4 drivers x {dense, QSGD} on the 2x4 mesh
# --------------------------------------------------------------------------


@needs8
def test_fed_chs_sharded_bit_parity():
    _run_pair(run_fed_chs, tiny_task(),
              FedCHSConfig(rounds=6, eval_every=3, seed=0), exact_loss=True)
    _run_pair(run_fed_chs, tiny_task(),
              FedCHSConfig(rounds=6, local_steps=4, local_epochs=2,
                           qsgd_levels=16, eval_every=3, seed=0))


@needs8
def test_fedavg_sharded_bit_parity():
    base = dict(rounds=4, local_steps=4, eval_every=2, seed=0)
    _run_pair(run_fedavg, tiny_task(), FedAvgConfig(**base))
    _run_pair(run_fedavg, tiny_task(), FedAvgConfig(**base, qsgd_levels=16))


@needs8
def test_wrwgd_sharded_bit_parity():
    """n=1 walk: degrades to replicated compute on the mesh, still exact."""
    _run_pair(run_wrwgd, tiny_task(),
              WRWGDConfig(rounds=6, local_steps=4, eval_every=3, seed=0),
              exact_loss=True)


@needs8
def test_hier_sharded_bit_parity():
    base = dict(rounds=4, local_steps=4, local_epochs=2, eval_every=2, seed=0)
    _run_pair(run_hier_local_qsgd, tiny_task(),
              HierLocalQSGDConfig(**base, qsgd_levels=16))
    _run_pair(run_hier_local_qsgd, tiny_task(),
              HierLocalQSGDConfig(**base, qsgd_levels=None))


@needs8
def test_ragged_clusters_sharded_bit_parity():
    """Ragged 7/5/4/4 clusters: every shard carries padded client slots whose
    zero gammas/masks must contribute exactly nothing."""
    _run_pair(run_fed_chs, tiny_task(ragged=True),
              FedCHSConfig(rounds=4, local_steps=4, local_epochs=2,
                           qsgd_levels=16, eval_every=2, seed=1))
    _run_pair(run_hier_local_qsgd, tiny_task(ragged=True),
              HierLocalQSGDConfig(rounds=2, local_steps=4, local_epochs=2,
                                  qsgd_levels=16, eval_every=1, seed=1))


@needs8
def test_sweep_seed_axis_sharded_bit_parity():
    """run_sweep(mesh=...) shards the leading SEED axis (pure GSPMD put):
    every per-seed trajectory is bit-identical to the unsharded sweep."""
    cfg = FedAvgConfig(rounds=4, local_steps=4, eval_every=2)
    rs0 = run_sweep(tiny_task(), cfg, range(8))
    rs1 = run_sweep(tiny_task(), cfg, range(8), mesh=_mesh())
    for a, b in zip(rs0, rs1):
        _check(a, b)


@needs8
def test_mlp_scale_tolerance_parity():
    """MNIST-MLP scale: the 784x200 GEMM is in XLA:CPU's width-dependent
    regime under forced host devices, so params are pinned at tight allclose
    (the divergence is lane-math, not sharding); ledger stays exact."""
    from repro.core.simulation import FLTask
    from repro.data import dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier

    ds = make_dataset("mnist", train_size=600, test_size=150, seed=0)
    clients = dirichlet_partition(ds.train_y, 8, 0.6, seed=0)
    clusters = [[0, 1, 2], [3, 4, 5], [6, 7]]
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    task = FLTask(model, ds, clients, clusters, batch_size=8, seed=0)

    cfg = FedAvgConfig(rounds=3, local_steps=3, eval_every=1, seed=0)
    r0 = run_fedavg(task, cfg)
    r1 = run_fedavg(task, dataclasses.replace(cfg, mesh=_mesh()))
    for a, b in zip(jax.tree.leaves(r0.final_params),
                    jax.tree.leaves(r1.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(r0.train_loss, r1.train_loss, rtol=1e-4, atol=0)
    assert r0.ledger.total_bits() == r1.ledger.total_bits()


# --------------------------------------------------------------------------
# structural properties of the sharded path
# --------------------------------------------------------------------------


@needs8
def test_sharded_chunk_zero_host_transfers():
    """The sharded hot loop stays on-device: executing a shard_map-wrapped
    chunk on pre-staged per-shard inputs performs zero implicit host<->device
    transfers under jax.transfer_guard("disallow")."""
    from repro.core.baselines.fedavg import _fedavg_scan_plan

    task = tiny_task()
    cfg = FedAvgConfig(rounds=4, local_steps=4, eval_every=10, chunk_rounds=4,
                       seed=0, mesh=_mesh())
    plan, _params_of, _traffic = _fedavg_scan_plan(task, task.source, cfg)
    assert plan.chunk_fn is not None and plan.xs_put is not None
    idxs = np.flatnonzero(np.asarray(plan.trained))
    xs = plan.xs_put(plan.stage(idxs))
    carry, consts = plan.carry, plan.consts
    # compile + warm outside the guard, on a sharding-preserving copy so
    # backends with buffer donation don't invalidate `carry`
    warm_carry = jax.tree.map(
        lambda leaf: jax.device_put(np.asarray(leaf), leaf.sharding), carry)
    warm = plan.chunk_fn(warm_carry, xs, consts)
    jax.block_until_ready(jax.tree.leaves(warm))
    with jax.transfer_guard("disallow"):
        out_carry, ys = plan.chunk_fn(carry, xs, consts)
        jax.block_until_ready(jax.tree.leaves((out_carry, ys)))


@needs8
def test_ambient_mesh_adoption():
    """mesh=None configs adopt an ambient ("clusters","clients") mesh via
    sharding.ctx; meshes with other axis names are never adopted."""
    from repro.launch.mesh import make_debug_mesh
    from repro.sharding.ctx import model_mesh

    fed = _mesh()
    assert resolve_mesh(None) is None
    with model_mesh(fed):
        assert resolve_mesh(None) is fed
    with model_mesh(make_debug_mesh(2, 4)):  # ("data","model"): not a fed mesh
        assert resolve_mesh(None) is None


@needs8
def test_mesh_with_telemetry_rejected():
    """Telemetry taps materialize at host chunk boundaries — incompatible
    with the device-sharded chunk; the combination must fail loudly."""
    from repro.obs import RunTelemetry

    cfg = FedAvgConfig(rounds=2, local_steps=2, eval_every=1, seed=0,
                       mesh=_mesh(), obs=RunTelemetry())
    with pytest.raises(AssertionError):
        run_fedavg(tiny_task(), cfg)


# --------------------------------------------------------------------------
# single-device behavior (any device count)
# --------------------------------------------------------------------------


def test_run_sweep_rejects_config_mesh():
    cfg = FedAvgConfig(rounds=2, local_steps=2, eval_every=1,
                       mesh=object())  # any non-None config.mesh
    with pytest.raises(AssertionError, match="run_sweep shards the seed axis"):
        run_sweep(tiny_task(), cfg, range(2))


def test_single_device_federation_mesh_is_inert():
    """A size-1 mesh resolves to None: the run takes the byte-for-byte
    single-device path (same jit cache entries, same results)."""
    m = make_federation_mesh(1, 1)
    assert m.axis_names == FED_AXES and resolve_mesh(m) is None
    cfg = FedAvgConfig(rounds=2, local_steps=2, eval_every=1, seed=0)
    r0 = run_fedavg(tiny_task(), cfg)
    r1 = run_fedavg(tiny_task(), dataclasses.replace(cfg, mesh=m))
    _check(r0, r1, exact_loss=True)


def test_federation_mesh_falls_back_with_warning():
    """Too few devices is an error, never a quiet single-device mesh: a
    multi-chip run must not silently run on one chip."""
    from repro.launch.mesh import make_debug_mesh

    n = jax.device_count()
    with pytest.raises(RuntimeError, match=f"needs {2 * n} devices, found {n}"):
        make_federation_mesh(2, n)
    with pytest.raises(RuntimeError, match=f"needs {2 * n} devices, found {n}"):
        make_debug_mesh(2, n)


def test_forced_8_devices_subprocess():
    """Re-run this file's multi-device cells under 8 forced host devices (the
    device count locks at backend init, so this needs a fresh process)."""
    if jax.device_count() >= 8:
        pytest.skip("cells ran directly")
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join("tests", "test_sharding_fed.py")],
        env=env, capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
