"""Seed-parity regression: the engine-driven algorithms must reproduce the
pre-refactor (host-level, per-interaction) loop implementations at fixed seed.

The reference implementations below are verbatim copies of the pre-engine
driver loops: eager per-interaction staging, per-interaction `float()` host
syncs, Python loops over clusters, `key, sub = jax.random.split(key)` chains.
Two intentional deviations: (1) the Hier-Local-QSGD ES->PS hop splits its
PRNG key per leaf (the historical implementation reused one subkey for every
layer — the bug class the Channel abstraction removes); (2) stacked client
uplinks compress per-sender with `fold_in(sub, slot)` keys and per-leaf
packed-wire block boundaries (the packed-QSGD refactor: a sender's encoding
is independent of how many senders share the stacked uplink, which is what
lets ragged clusters run under the whole-run scan).  The references mirror
both via `qsgd_compress_tree` under an explicit per-sender vmap.

Tolerance: losses within 1e-5, accuracies within 1e-5 (test-set accuracy is
quantized in steps of 1/test_size, so this effectively requires identical
predictions). QSGD cases run short horizons: stochastic rounding (`floor`)
can amplify sub-ulp compiler-fusion differences into level flips over long
runs, but short fixed-seed trajectories are stable.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import FedCHSConfig, run_fed_chs
from repro.core.baselines import (
    FedAvgConfig,
    HierLocalQSGDConfig,
    WRWGDConfig,
    run_fedavg,
    run_hier_local_qsgd,
    run_wrwgd,
)
from repro.core.oracles import cluster_sgd, local_sgd, multi_client_local_sgd
from repro.core.scheduler import FedCHSScheduler
from repro.core.simulation import evaluate
from repro.core.topology import make_topology
from repro.kernels.ops import qsgd_compress_tree
from repro.optim.schedules import paper_sqrt_schedule
from repro.utils import tree_add


def _compress_stacked(deltas, sub, levels):
    """The engine's stacked-uplink keying (see `engine.compress_uplinks`):
    sender slot i compresses under fold_in(sub, i), so its message is
    independent of the stacked width."""
    n = jax.tree.leaves(deltas)[0].shape[0]
    return jax.vmap(
        lambda d, i: qsgd_compress_tree(d, jax.random.fold_in(sub, i), s=levels)
    )(deltas, jnp.arange(n))


def _assert_trajectories_match(ref, new, atol=1e-5):
    ref_rounds, ref_acc, ref_loss = ref
    assert ref_rounds == new.rounds
    np.testing.assert_allclose(new.train_loss, ref_loss, atol=atol, rtol=0)
    np.testing.assert_allclose(new.test_acc, ref_acc, atol=atol, rtol=0)


# --------------------------------------------------------------------------
# reference implementations (pre-refactor loop structure)
# --------------------------------------------------------------------------


def ref_fed_chs(task, config):
    task.reset_loaders(config.seed)
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.array([sched_fn(k) for k in range(K)], dtype=np.float32)

    topo = make_topology(config.topology, task.num_clusters, seed=config.topology_seed)
    rng = np.random.default_rng(config.seed)
    m0 = (
        int(rng.integers(task.num_clusters))
        if config.initial_cluster is None
        else config.initial_cluster
    )
    scheduler = FedCHSScheduler(topo, task.cluster_sizes, initial=m0)

    params = task.init_params()
    cluster_phase = cluster_sgd(task.model)
    multi_local = multi_client_local_sgd(task.model)
    key = jax.random.PRNGKey(config.seed + 1)

    rounds_log, acc_log, loss_log = [], [], []
    m = scheduler.state.current
    for t in range(config.rounds):
        gammas = jnp.asarray(task.cluster_weights(m))
        if E == 1 and config.qsgd_levels is None:
            b = task.sample_cluster_batches(m, K)
            params, loss = cluster_phase(params, b["x"], b["y"], gammas, jnp.asarray(lrs))
        else:
            loss_acc = 0.0
            for j in range(interactions):
                lr_slice = jnp.asarray(lrs[j * E : (j + 1) * E])
                b = task.sample_cluster_batches(m, E)
                xs = jnp.swapaxes(b["x"], 0, 1)
                ys = jnp.swapaxes(b["y"], 0, 1)
                new_p, losses = multi_local(params, xs, ys, lr_slice)
                deltas = jax.tree.map(lambda np_, op: np_ - op[None], new_p, params)
                if config.qsgd_levels is not None:
                    key, sub = jax.random.split(key)
                    deltas = _compress_stacked(deltas, sub, config.qsgd_levels)
                agg = jax.tree.map(lambda dl: jnp.einsum("n,n...->...", gammas, dl), deltas)
                params = tree_add(params, agg)
                loss_acc += float(jnp.mean(losses))
            loss = loss_acc / interactions

        m = scheduler.advance()
        if t % config.eval_every == 0 or t == config.rounds - 1:
            rounds_log.append(t)
            acc_log.append(evaluate(task.model, params, task.dataset))
            loss_log.append(float(loss))
    return rounds_log, acc_log, loss_log


def ref_fedavg(task, config):
    task.reset_loaders(config.seed)
    K = config.local_steps
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = jnp.asarray([sched_fn(k) for k in range(K)], dtype=jnp.float32)

    params = task.init_params()
    multi_local = multi_client_local_sgd(task.model)
    gammas = jnp.asarray(task.global_weights())
    key = jax.random.PRNGKey(config.seed + 1)

    rounds_log, acc_log, loss_log = [], [], []
    n = task.num_clients
    for t in range(config.rounds):
        per_client = [task.sample_client_batches(i, K) for i in range(n)]
        xs = jnp.stack([b["x"] for b in per_client])
        ys = jnp.stack([b["y"] for b in per_client])
        new_p, losses = multi_local(params, xs, ys, lrs)
        deltas = jax.tree.map(lambda np_, op: np_ - op[None], new_p, params)
        if config.qsgd_levels is not None:
            key, sub = jax.random.split(key)
            deltas = _compress_stacked(deltas, sub, config.qsgd_levels)
        agg = jax.tree.map(lambda dl: jnp.einsum("n,n...->...", gammas, dl), deltas)
        params = tree_add(params, agg)

        if t % config.eval_every == 0 or t == config.rounds - 1:
            rounds_log.append(t)
            acc_log.append(evaluate(task.model, params, task.dataset))
            loss_log.append(float(jnp.mean(losses)))
    return rounds_log, acc_log, loss_log


def ref_wrwgd(task, config):
    task.reset_loaders(config.seed)
    K = config.local_steps
    # the walk's decaying schedule is indexed by the GLOBAL round t (constant
    # over the K local steps of one visit) — see wrwgd._walk_round_lrs
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)

    topo = make_topology(config.topology, task.num_clients, seed=config.topology_seed)
    rng = np.random.default_rng(config.seed)
    current = int(rng.integers(task.num_clients))

    params = task.init_params()
    local = local_sgd(task.model)

    rounds_log, acc_log, loss_log = [], [], []
    for t in range(config.rounds):
        b = task.sample_client_batches(current, K)
        lrs_t = jnp.full((K,), sched_fn(t), dtype=jnp.float32)
        params, loss = local(params, b["x"], b["y"], lrs_t)

        nbrs = list(topo.neighbors(current))
        if config.weighting == "data_size":
            w = task.client_sizes[nbrs]
            w = w / w.sum()
        else:
            w = np.full(len(nbrs), 1.0 / len(nbrs))
        current = int(rng.choice(nbrs, p=w))

        if t % config.eval_every == 0 or t == config.rounds - 1:
            rounds_log.append(t)
            acc_log.append(evaluate(task.model, params, task.dataset))
            loss_log.append(float(loss))
    return rounds_log, acc_log, loss_log


def ref_hier_local_qsgd(task, config):
    task.reset_loaders(config.seed)
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.asarray([sched_fn(k) for k in range(K)], dtype=np.float32)

    params = task.init_params()
    multi_local = multi_client_local_sgd(task.model)
    key = jax.random.PRNGKey(config.seed + 1)

    M = task.num_clusters
    cluster_gammas = [jnp.asarray(task.cluster_weights(m)) for m in range(M)]
    es_weights = jnp.asarray(
        np.array(task.cluster_sizes, dtype=np.float32) / sum(task.cluster_sizes)
    )

    rounds_log, acc_log, loss_log = [], [], []
    for t in range(config.rounds):
        cluster_params = [params] * M
        loss_acc = 0.0
        for j in range(interactions):
            lr_slice = jnp.asarray(lrs[j * E : (j + 1) * E])
            for m in range(M):
                b = task.sample_cluster_batches(m, E)
                xs = jnp.swapaxes(b["x"], 0, 1)
                ys = jnp.swapaxes(b["y"], 0, 1)
                new_p, losses = multi_local(cluster_params[m], xs, ys, lr_slice)
                deltas = jax.tree.map(
                    lambda np_, op: np_ - op[None], new_p, cluster_params[m]
                )
                if config.qsgd_levels is not None:
                    key, sub = jax.random.split(key)
                    deltas = _compress_stacked(deltas, sub, config.qsgd_levels)
                agg = jax.tree.map(
                    lambda dl, g=cluster_gammas[m]: jnp.einsum("n,n...->...", g, dl),
                    deltas,
                )
                cluster_params[m] = tree_add(cluster_params[m], agg)
                loss_acc += float(jnp.mean(losses))

        es_deltas = []
        for m in range(M):
            delta = jax.tree.map(lambda a, b: a - b, cluster_params[m], params)
            if config.qsgd_levels is not None:
                key, sub = jax.random.split(key)
                # per-leaf key split (the fixed ES->PS behavior)
                delta = qsgd_compress_tree(delta, sub, s=config.qsgd_levels)
            es_deltas.append(delta)
        stacked = jax.tree.map(lambda *xs_: jnp.stack(xs_), *es_deltas)
        agg = jax.tree.map(lambda x: jnp.einsum("m,m...->...", es_weights, x), stacked)
        params = tree_add(params, agg)

        if t % config.eval_every == 0 or t == config.rounds - 1:
            rounds_log.append(t)
            acc_log.append(evaluate(task.model, params, task.dataset))
            loss_log.append(loss_acc / (interactions * M))
    return rounds_log, acc_log, loss_log


# --------------------------------------------------------------------------
# parity assertions
# --------------------------------------------------------------------------


def test_fed_chs_grad_mode_parity(small_task):
    cfg = FedCHSConfig(rounds=5, local_steps=6, eval_every=2, seed=3)
    _assert_trajectories_match(ref_fed_chs(small_task, cfg), run_fed_chs(small_task, cfg))


def test_fed_chs_local_epochs_parity(small_task):
    cfg = FedCHSConfig(rounds=4, local_steps=6, local_epochs=3, eval_every=2, seed=1)
    _assert_trajectories_match(ref_fed_chs(small_task, cfg), run_fed_chs(small_task, cfg))


def test_fed_chs_qsgd_parity(small_task):
    cfg = FedCHSConfig(rounds=3, local_steps=4, local_epochs=2, qsgd_levels=16,
                       eval_every=1, seed=0)
    _assert_trajectories_match(ref_fed_chs(small_task, cfg), run_fed_chs(small_task, cfg))


def test_fedavg_parity(small_task):
    cfg = FedAvgConfig(rounds=3, local_steps=5, qsgd_levels=8, eval_every=1, seed=2)
    _assert_trajectories_match(ref_fedavg(small_task, cfg), run_fedavg(small_task, cfg))


def test_fedavg_dense_parity(small_task):
    cfg = FedAvgConfig(rounds=3, local_steps=5, eval_every=1, seed=0)
    _assert_trajectories_match(ref_fedavg(small_task, cfg), run_fedavg(small_task, cfg))


def test_wrwgd_parity(small_task):
    cfg = WRWGDConfig(rounds=8, local_steps=5, eval_every=3, seed=4)
    _assert_trajectories_match(ref_wrwgd(small_task, cfg), run_wrwgd(small_task, cfg))


def test_hier_local_qsgd_parity(small_task):
    # small_task has equal-size clusters, so the padded/masked vmapped round
    # is sample-for-sample identical to the sequential per-cluster loop
    cfg = HierLocalQSGDConfig(rounds=2, local_steps=4, local_epochs=2,
                              qsgd_levels=16, eval_every=1, seed=0)
    _assert_trajectories_match(
        ref_hier_local_qsgd(small_task, cfg), run_hier_local_qsgd(small_task, cfg)
    )


def test_hier_local_dense_parity(small_task):
    cfg = HierLocalQSGDConfig(rounds=2, local_steps=4, local_epochs=2,
                              qsgd_levels=None, eval_every=1, seed=5)
    _assert_trajectories_match(
        ref_hier_local_qsgd(small_task, cfg), run_hier_local_qsgd(small_task, cfg)
    )


def test_hier_parity_with_ragged_clusters():
    """Ragged cluster sizes exercise the padding/masking path. Dense channel:
    padded slots must contribute exactly nothing."""
    from repro.core.simulation import FLTask
    from repro.data import dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier

    ds = make_dataset("mnist", train_size=1200, test_size=300, seed=1)
    clients = dirichlet_partition(ds.train_y, 7, 0.6, seed=1)
    clusters = [[0, 1, 2], [3, 4], [5, 6]]  # ragged: 3/2/2
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    task = FLTask(model, ds, clients, clusters, batch_size=16, seed=1)

    cfg = HierLocalQSGDConfig(rounds=2, local_steps=4, local_epochs=2,
                              qsgd_levels=None, eval_every=1, seed=0)
    _assert_trajectories_match(
        ref_hier_local_qsgd(task, cfg), run_hier_local_qsgd(task, cfg)
    )


# --------------------------------------------------------------------------
# participation parity: FullParticipation must be *bit-identical* to the
# no-sampler path (params, losses, ledger totals) for all four drivers
# --------------------------------------------------------------------------

from repro.part import FullParticipation  # noqa: E402


def _assert_bit_identical(a, b):
    assert a.rounds == b.rounds
    assert a.train_loss == b.train_loss      # float() of the same arrays
    assert a.test_acc == b.test_acc
    assert a.ledger.bits == b.ledger.bits
    assert a.ledger.messages == b.ledger.messages
    assert a.ledger.events == b.ledger.events
    for la, lb in zip(jax.tree.leaves(a.final_params), jax.tree.leaves(b.final_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _full_participation_cases(seed=0, qsgd=None):
    return [
        (run_fed_chs, FedCHSConfig, dict(rounds=3, local_steps=4, local_epochs=2,
                                         eval_every=1, seed=seed, qsgd_levels=qsgd)),
        (run_fedavg, FedAvgConfig, dict(rounds=2, local_steps=3, eval_every=1,
                                        seed=seed, qsgd_levels=qsgd)),
        (run_wrwgd, WRWGDConfig, dict(rounds=4, local_steps=3, eval_every=2,
                                      seed=seed)),
        (run_hier_local_qsgd, HierLocalQSGDConfig,
         dict(rounds=2, local_steps=4, local_epochs=2, eval_every=1, seed=seed,
              qsgd_levels=qsgd)),
    ]


def _assert_full_participation_parity(task, seed=0, qsgd=None):
    for run, cfg_cls, kwargs in _full_participation_cases(seed, qsgd):
        base = run(task, cfg_cls(**kwargs))
        sampled = run(task, cfg_cls(**kwargs, sampler=FullParticipation()))
        _assert_bit_identical(base, sampled)


def test_full_participation_is_bit_identical_all_drivers(small_task):
    _assert_full_participation_parity(small_task, seed=0, qsgd=None)


def test_full_participation_is_bit_identical_with_qsgd(small_task):
    _assert_full_participation_parity(small_task, seed=3, qsgd=8)


# hypothesis-randomized versions (cluster shapes x channels x seeds); the
# deterministic cases above always run, so the parity contract is pinned even
# where hypothesis is absent — CI passes --require-hypothesis to guarantee
# these actually execute there (see tests/conftest.py)
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:
    import functools

    # drawn from small fixed menus (and tasks cached per shape, sharing one
    # model instance) so jit re-compiles stay bounded across examples
    _SHAPES = (
        ((0, 1, 2), (3, 4), (5, 6)),          # ragged 3/2/2
        ((0, 1), (2, 3), (4, 5), (6,)),       # ragged with a singleton
        ((0, 1, 2, 3), (4, 5, 6)),            # two fat clusters
    )
    _CHANNELS = [None, 8, 16]  # qsgd_levels (None = dense)

    @functools.lru_cache(maxsize=None)
    def _prop_task(shape):
        from repro.core.simulation import FLTask
        from repro.data import dirichlet_partition, make_dataset
        from repro.models.classifier import make_classifier

        ds = make_dataset("mnist", train_size=700, test_size=150, seed=1)
        clients = dirichlet_partition(ds.train_y, 7, 0.6, seed=1)
        model = _prop_task.model  # one model instance -> one engine cache entry
        if model is None:
            model = _prop_task.model = make_classifier(
                "mlp", "mnist", ds.spec.image_shape, 10)
        return FLTask(model, ds, clients, [list(c) for c in shape],
                      batch_size=8, seed=1)

    _prop_task.model = None

    @given(shape=st.sampled_from(_SHAPES), seed=st.integers(0, 20),
           qsgd=st.sampled_from(_CHANNELS))
    @settings(max_examples=5, deadline=None)
    def test_property_full_participation_parity(shape, seed, qsgd):
        _assert_full_participation_parity(_prop_task(shape), seed=seed, qsgd=qsgd)

    @given(seed=st.integers(0, 50),
           mask_bits=st.lists(st.booleans(), min_size=5, max_size=5))
    @settings(max_examples=10, deadline=None)
    def test_property_mask_freezes_dropped_opt_state(small_task, seed, mask_bits):
        """Any mask pattern leaves dropped clients' LocalOpt state unchanged."""
        from repro.comm.channels import DenseChannel
        from repro.core.engine import RoundEngine
        from repro.optim.local import MomentumSGD

        engine = RoundEngine(small_task.model, DenseChannel(),
                             local_opt=MomentumSGD())
        small_task.reset_loaders(seed)
        members = small_task.cluster_members[0]
        n = len(members)
        mask = np.asarray(mask_bits[:n], np.float32)
        params = small_task.init_params()
        gammas = np.asarray(small_task.cluster_weights(0))
        lrs = jnp.full((2, 2), 0.05, jnp.float32)
        batch = small_task.sample_round_batches(0, 4, 2)
        opt0 = engine.init_opt_state(params, n)
        # warm round so the momentum state is nonzero
        params, opt1, _ = engine.cluster_round(params, batch, jnp.asarray(gammas),
                                               lrs, None, opt0)
        w = gammas * mask
        gammas_r = jnp.asarray(w / w.sum() if w.sum() > 0 else w)
        batch2 = small_task.sample_round_batches(0, 4, 2)
        _, opt2, _ = engine.cluster_round(params, batch2, gammas_r, lrs, None,
                                          opt1, mask=mask)
        for before, after in zip(jax.tree.leaves(opt1), jax.tree.leaves(opt2)):
            for i in range(n):
                if not mask[i]:
                    np.testing.assert_array_equal(np.asarray(after[i]),
                                                  np.asarray(before[i]))


# --------------------------------------------------------------------------
# whole-run scan parity: the scanned executor (scan_rounds=True, the default)
# must reproduce the looped path at fixed seed — params bit-identical,
# eval metrics exactly equal, ledger (aggregates + event stream + history)
# identical; reported loss scalars may differ by reduction-fusion ulps
# across the scan boundary, hence the 1e-5 tolerance
# --------------------------------------------------------------------------

import dataclasses  # noqa: E402

from repro.comm.channels import TopKChannel  # noqa: E402
from repro.part import AvailabilityAware, BernoulliTrace, UniformK  # noqa: E402


def _assert_scan_matches_loop(run, task, cfg):
    a = run(task, dataclasses.replace(cfg, scan_rounds=True))
    b = run(task, dataclasses.replace(cfg, scan_rounds=False))
    assert a.rounds == b.rounds
    assert a.test_acc == b.test_acc
    np.testing.assert_allclose(a.train_loss, b.train_loss, atol=1e-5, rtol=0)
    for la, lb in zip(jax.tree.leaves(a.final_params), jax.tree.leaves(b.final_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert a.ledger.bits == b.ledger.bits
    assert a.ledger.messages == b.ledger.messages
    assert a.ledger.events == b.ledger.events
    assert a.ledger.history == b.ledger.history


_CHURN = AvailabilityAware(BernoulliTrace(p=0.4, seed=9))       # pass-through rounds
_DARK = AvailabilityAware(BernoulliTrace(p=0.15, seed=3))       # mostly-dark rounds


def test_scan_parity_fed_chs_grad_mode(small_task):
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(rounds=6, local_steps=6, eval_every=2,
                                           seed=3, chunk_rounds=2))


def test_scan_parity_fed_chs_channels(small_task):
    base = dict(rounds=4, local_steps=4, local_epochs=2, eval_every=1, seed=0)
    _assert_scan_matches_loop(run_fed_chs, small_task, FedCHSConfig(**base))
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, qsgd_levels=16))
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, channel=TopKChannel(0.1)))


def test_scan_parity_fed_chs_samplers(small_task):
    base = dict(rounds=8, local_steps=4, local_epochs=2, eval_every=3, seed=2)
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, sampler=UniformK(k=2, seed=5)))
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, sampler=_CHURN))
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, sampler=_DARK, qsgd_levels=8))
    _assert_scan_matches_loop(run_fed_chs, small_task,
                              FedCHSConfig(**base, sampler=_CHURN,
                                           availability_scheduler=True))


def test_scan_parity_fed_chs_dynamic_topologies(small_task):
    """Dynamic networks (IoV rewiring, LEO visibility windows) were the last
    scan_rounds=False fallback.  The graph sequence is a seed-deterministic
    function of the round index, so `Scheduler.precompute(dynamic=...)`
    replays the whole visit order host-side — swapping in `dynamic(t)`
    exactly where the looped driver calls `set_topology` — and the scanned
    executor runs dynamic cells like any static topology."""
    for dyn in ("iov", "leo"):
        _assert_scan_matches_loop(run_fed_chs, small_task,
                                  FedCHSConfig(rounds=6, local_steps=6,
                                               eval_every=2, seed=1,
                                               dynamic=dyn))
        _assert_scan_matches_loop(run_fed_chs, small_task,
                                  FedCHSConfig(rounds=4, local_steps=4,
                                               local_epochs=2, eval_every=2,
                                               seed=0, dynamic=dyn,
                                               qsgd_levels=16))


def test_scan_parity_fedavg(small_task):
    _assert_scan_matches_loop(run_fedavg, small_task,
                              FedAvgConfig(rounds=3, local_steps=5, qsgd_levels=8,
                                           eval_every=1, seed=2))
    _assert_scan_matches_loop(run_fedavg, small_task,
                              FedAvgConfig(rounds=3, local_steps=5, eval_every=1,
                                           seed=0, channel=TopKChannel(0.05)))
    _assert_scan_matches_loop(run_fedavg, small_task,
                              FedAvgConfig(rounds=8, local_steps=3, eval_every=3,
                                           seed=2, sampler=_DARK))


def test_scan_parity_wrwgd(small_task):
    _assert_scan_matches_loop(run_wrwgd, small_task,
                              WRWGDConfig(rounds=8, local_steps=5, eval_every=3, seed=4))
    _assert_scan_matches_loop(run_wrwgd, small_task,
                              WRWGDConfig(rounds=10, local_steps=4, eval_every=3,
                                          seed=4, sampler=_DARK, chunk_rounds=3))


def test_scan_parity_hier(small_task):
    _assert_scan_matches_loop(run_hier_local_qsgd, small_task,
                              HierLocalQSGDConfig(rounds=2, local_steps=4,
                                                  local_epochs=2, qsgd_levels=16,
                                                  eval_every=1, seed=0))
    _assert_scan_matches_loop(run_hier_local_qsgd, small_task,
                              HierLocalQSGDConfig(rounds=6, local_steps=4,
                                                  local_epochs=2, qsgd_levels=16,
                                                  eval_every=2, seed=2,
                                                  sampler=_CHURN, chunk_rounds=2))
    _assert_scan_matches_loop(run_hier_local_qsgd, small_task,
                              HierLocalQSGDConfig(rounds=3, local_steps=4,
                                                  local_epochs=2, qsgd_levels=16,
                                                  es_channel=TopKChannel(0.1),
                                                  eval_every=1, seed=1))


def test_scan_parity_ragged_clusters_padding_exact():
    """Ragged clusters exercise the scanned path's padded slots.  Every
    channel is padding-invariant now — Dense (identity), per-message Top-K,
    and packed-wire QSGD/sign-SGD (per-leaf block boundaries + per-sender
    fold_in keys), so the PR-5-era QSGD fall-back-to-looped gate is gone:
    Fed-CHS+QSGD on ragged clusters runs scanned, bit-identically."""
    from repro.comm.channels import SignSGDChannel
    from repro.core.fed_chs import _fed_chs_scannable
    from repro.core.simulation import FLTask
    from repro.data import dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier

    ds = make_dataset("mnist", train_size=1200, test_size=300, seed=1)
    clients = dirichlet_partition(ds.train_y, 7, 0.6, seed=1)
    clusters = [[0, 1, 2], [3, 4], [5, 6]]  # ragged: 3/2/2
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    task = FLTask(model, ds, clients, clusters, batch_size=16, seed=1)

    _assert_scan_matches_loop(run_fed_chs, task,
                              FedCHSConfig(rounds=5, local_steps=6, local_epochs=3,
                                           eval_every=2, seed=1))
    _assert_scan_matches_loop(run_fed_chs, task,
                              FedCHSConfig(rounds=4, local_steps=4, local_epochs=2,
                                           channel=TopKChannel(0.1), eval_every=1,
                                           seed=0))
    # the cell PR 5 had to gate out: stochastic QSGD on ragged clusters
    _assert_scan_matches_loop(run_fed_chs, task,
                              FedCHSConfig(rounds=4, local_steps=4, local_epochs=2,
                                           qsgd_levels=16, eval_every=1, seed=2))
    _assert_scan_matches_loop(run_fed_chs, task,
                              FedCHSConfig(rounds=3, local_steps=4, local_epochs=2,
                                           channel=SignSGDChannel(), eval_every=1,
                                           seed=3))
    assert _fed_chs_scannable(task, FedCHSConfig(qsgd_levels=16))
    assert _fed_chs_scannable(task, FedCHSConfig())


def test_scanned_hot_loop_zero_host_transfers(small_task):
    """Between eval points the scanned executor's hot loop is ONE compiled
    chunk call on pre-staged device inputs: with jax.transfer_guard
    ("disallow") active, executing a chunk performs zero implicit
    host<->device transfers."""
    from repro.core.engine import scan_chunk_fn
    from repro.core.fed_chs import _fed_chs_scan_plan

    cfg = FedCHSConfig(rounds=6, local_steps=4, local_epochs=2, eval_every=10,
                       chunk_rounds=6, seed=0)
    plan, _params_of, _traffic = _fed_chs_scan_plan(small_task, small_task.source, cfg)
    idxs = np.flatnonzero(np.asarray(plan.trained))
    xs = jax.device_put(plan.stage(idxs))
    carry = jax.device_put(plan.carry)
    consts = jax.device_put(plan.consts)
    chunk = scan_chunk_fn(plan.body)
    # compile outside the guard (compilation may stage constants); warm on a
    # copy so backends with buffer donation don't invalidate `carry`
    warm = chunk(jax.tree.map(jnp.array, carry), xs, consts)
    jax.block_until_ready(jax.tree.leaves(warm))
    with jax.transfer_guard("disallow"):
        out_carry, losses = chunk(carry, xs, consts)
        jax.block_until_ready(jax.tree.leaves((out_carry, losses)))


# --------------------------------------------------------------------------
# telemetry parity: the in-graph taps are READ-ONLY — a run with
# obs=RunTelemetry() must be bit-identical (params, metrics, losses, ledger)
# to the same run with obs=None, for all four drivers, scanned and looped
# --------------------------------------------------------------------------

from repro.obs import RunTelemetry  # noqa: E402


def _assert_telemetry_neutral(task, run, cfg_cls, kwargs):
    base = run(task, cfg_cls(**kwargs))
    obs = RunTelemetry()
    tapped = run(task, cfg_cls(**kwargs, obs=obs))
    _assert_bit_identical(base, tapped)
    assert base.telemetry is None and tapped.telemetry is obs
    # full participation: every round trains, so every round is tapped
    assert obs.rounds == list(range(kwargs["rounds"]))
    for k in ("update_norm", "drift", "comp_err", "mass"):
        assert len(obs.metrics[k]) == kwargs["rounds"]
    assert obs.tracer.events  # spans were recorded


def test_telemetry_is_bit_neutral_all_drivers_scanned(small_task):
    for run, cfg_cls, kwargs in _full_participation_cases(seed=1, qsgd=8):
        _assert_telemetry_neutral(small_task, run, cfg_cls, kwargs)


def test_telemetry_is_bit_neutral_all_drivers_looped(small_task):
    for run, cfg_cls, kwargs in _full_participation_cases(seed=2, qsgd=None):
        _assert_telemetry_neutral(small_task, run, cfg_cls,
                                  dict(kwargs, scan_rounds=False))


def test_tapped_scanned_hot_loop_zero_host_transfers(small_task):
    """The tapped chunk accumulates telemetry ON DEVICE: with
    jax.transfer_guard("disallow") active, executing a tapped chunk still
    performs zero implicit host<->device transfers (materialization happens
    at the chunk boundary via RunTelemetry.record_stacked, outside the
    guard)."""
    from repro.core.engine import scan_chunk_fn
    from repro.core.fed_chs import _fed_chs_scan_plan

    cfg = FedCHSConfig(rounds=6, local_steps=4, local_epochs=2, eval_every=10,
                       chunk_rounds=6, seed=0, obs=RunTelemetry())
    plan, _params_of, _traffic = _fed_chs_scan_plan(small_task, small_task.source, cfg)
    idxs = np.flatnonzero(np.asarray(plan.trained))
    xs = jax.device_put(plan.stage(idxs))
    carry = jax.device_put(plan.carry)
    consts = jax.device_put(plan.consts)
    chunk = scan_chunk_fn(plan.body)
    warm = chunk(jax.tree.map(jnp.array, carry), xs, consts)
    jax.block_until_ready(jax.tree.leaves(warm))
    with jax.transfer_guard("disallow"):
        out_carry, (losses, tele) = chunk(carry, xs, consts)
        jax.block_until_ready(jax.tree.leaves((out_carry, losses, tele)))
    assert set(tele) == {"update_norm", "drift", "comp_err", "mass"}


if HAS_HYPOTHESIS:

    @given(seed=st.integers(0, 30), qsgd=st.sampled_from([None, 8]),
           p=st.sampled_from([None, 0.7, 0.3]))
    @settings(max_examples=5, deadline=None)
    def test_property_scan_loop_parity(seed, qsgd, p):
        """Random (seed, channel, churn) — scanned == looped for Fed-CHS and
        FedAvg on a cached ragged-cluster task (QSGD on ragged clusters now
        runs the real scanned path: packed-wire blocks are per-leaf and keys
        per-sender, so padding to n_max cannot change any message)."""
        task = _prop_task(_SHAPES[seed % len(_SHAPES)])
        sampler = None if p is None else AvailabilityAware(BernoulliTrace(p=p, seed=seed))
        _assert_scan_matches_loop(
            run_fed_chs, task,
            FedCHSConfig(rounds=4, local_steps=4, local_epochs=2, eval_every=2,
                         seed=seed, qsgd_levels=qsgd, sampler=sampler))
        _assert_scan_matches_loop(
            run_fedavg, task,
            FedAvgConfig(rounds=3, local_steps=3, eval_every=1, seed=seed,
                         qsgd_levels=qsgd, sampler=sampler))


# --------------------------------------------------------------------------
# client_microbatch parity: the group-scanned engine vs the all-clients vmap.
# Both modes agree exactly at microbatch == n (one group is the full vmap).
# Below it, grad mode feeds the same einsum, but XLA:CPU folds the vmapped
# clients into the shapes of the matmuls, and a GEMM of another shape may sum
# in another order (seen at width 1: ~1e-7 in a local grad), so params agree
# to the same bound as delta mode.  Delta mode also re-associates the
# gamma-weighted aggregation (acc += einsum per group), so params/opt-state
# agree to <=1 ulp of the aggregate (atol 3e-6 at MLP scale).
# --------------------------------------------------------------------------

from repro.comm.channels import DenseChannel, QSGDChannel, SignSGDChannel  # noqa: E402
from repro.core.engine import RoundEngine, split_chain  # noqa: E402
from repro.optim.local import MomentumSGD  # noqa: E402


def test_microbatch_grad_mode_bit_parity(small_task):
    task = small_task
    n = len(task.cluster_members[0])
    params = task.init_params()
    gammas = jnp.asarray(task.cluster_weights(0))
    lrs = jnp.full((6,), 0.05, jnp.float32)
    task.reset_loaders(0)
    batch = task.sample_cluster_batches(0, 6)
    p_ref, l_ref = RoundEngine(task.model).grad_round(params, batch, gammas, lrs)
    for mb in (1, 2, 3, n):
        eng = RoundEngine(task.model, client_microbatch=mb)
        p_mb, l_mb = eng.grad_round(params, batch, gammas, lrs)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_mb)):
            if mb == n:  # one group: the very same vmap
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=3e-6)
        if mb == n:
            np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_mb))
        else:
            np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_mb),
                                       rtol=0, atol=1e-6)


def test_microbatch_delta_mode_one_ulp(small_task):
    task = small_task
    n = len(task.cluster_members[0])
    params = task.init_params()
    gammas = jnp.asarray(task.cluster_weights(0))
    lrs = jnp.full((3, 2), 0.05, jnp.float32)
    for channel in (DenseChannel(), QSGDChannel(8), SignSGDChannel()):
        task.reset_loaders(0)
        batch = task.sample_round_batches(0, 6, 2)
        _, subs = split_chain(jax.random.PRNGKey(7), 3)
        base = RoundEngine(task.model, channel, local_opt=MomentumSGD())
        opt0 = base.init_opt_state(params, n)
        p_ref, s_ref, l_ref = base.cluster_round(params, batch, gammas, lrs,
                                                 subs, opt0)
        for mb in (1, 2, n):
            eng = RoundEngine(task.model, channel, local_opt=MomentumSGD(),
                              client_microbatch=mb)
            p_mb, s_mb, l_mb = eng.cluster_round(params, batch, gammas, lrs,
                                                 subs, opt0)
            for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_mb)):
                if mb == n:  # one group: the accumulator adds exactly once
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                else:
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=0, atol=3e-6)
            for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_mb)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=3e-6)
            np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_mb),
                                       rtol=0, atol=1e-6)


if HAS_HYPOTHESIS:
    _MB_CHANNELS = {"dense": DenseChannel(), "qsgd8": QSGDChannel(8),
                    "sign": SignSGDChannel()}

    @given(shape=st.sampled_from(_SHAPES), seed=st.integers(0, 10),
           kind=st.sampled_from(sorted(_MB_CHANNELS)),
           mb=st.sampled_from((1, 2, None)))
    @settings(max_examples=8, deadline=None)
    def test_property_microbatch_delta_parity(shape, seed, kind, mb):
        """Ragged clusters x {Dense, QSGD, SignSGD} x microbatch {1, 2, n}:
        the microbatched cluster round tracks the vmapped one to <=1 ulp of
        the aggregate (slot-keyed uplink rng makes QSGD messages identical
        across group widths)."""
        task = _prop_task(shape)
        n = len(task.cluster_members[0])
        mb_val = n if mb is None else mb
        channel = _MB_CHANNELS[kind]
        params = task.init_params()
        gammas = jnp.asarray(task.cluster_weights(0))
        lrs = jnp.full((2, 2), 0.05, jnp.float32)
        task.reset_loaders(seed)
        batch = task.sample_round_batches(0, 4, 2)
        _, subs = split_chain(jax.random.PRNGKey(seed), 2)
        base = RoundEngine(task.model, channel, local_opt=MomentumSGD())
        opt0 = base.init_opt_state(params, n)
        p_ref, s_ref, l_ref = base.cluster_round(params, batch, gammas, lrs,
                                                 subs, opt0)
        eng = RoundEngine(task.model, channel, local_opt=MomentumSGD(),
                          client_microbatch=mb_val)
        p_mb, s_mb, l_mb = eng.cluster_round(params, batch, gammas, lrs,
                                             subs, opt0)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_mb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=3e-6)
        for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_mb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=3e-6)
        np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_mb),
                                   rtol=0, atol=1e-6)
