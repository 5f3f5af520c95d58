"""Shared FL-simulation machinery: FedTask bundling, round staging, evaluation.

Every algorithm (Fed-CHS and the three baselines) consumes an `FLTask` and
produces a `RunResult`.  The task is generic over the workload: its model is
any `FedModel` (a raw Appendix-A `Classifier` is wrapped automatically), its
batches come from any `DataSource` (array classification shards or per-client
token streams), and its metric is whatever the model's `eval_metric` computes
— accuracy for classifiers, perplexity for LMs.  The jitted inner loops live
in `core/oracles.py` / `core/engine.py` and are shared, so quality
comparisons are apples-to-apples across algorithms AND workloads.

Staging helpers return *batch pytrees* (never bare (xs, ys) pairs) whose
leaves carry the engine's documented leading axes, e.g. ``(J, n, E, B, ...)``
for one delta-mode round.  The classifier path stages through the same
`ClientLoader` rng chain as before the FedTask refactor, so fixed-seed
trajectories are bit-identical (tests/test_engine_parity.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ledger import CommLedger
from repro.data.partition import ClientData
from repro.data.sources import ArraySource, DataSource
from repro.data.synthetic import Dataset
from repro.models.classifier import Classifier
from repro.models.fed import FedModel, as_fed_model
from repro.obs.trace import maybe_span
from repro.utils import tree_num_params

PyTree = Any
Batch = Any


def _stack_batches(batches: list[Batch]) -> Batch:
    """Stack a list of equal-structure batch pytrees along a new leading axis."""
    return jax.tree.map(lambda *leaves: np.stack(leaves), *batches)


@dataclasses.dataclass
class FLTask:
    """Everything an FL algorithm needs to run one experiment.

    Classifier construction is unchanged: ``FLTask(clf, dataset, clients,
    cluster_members, batch_size)`` builds an `ArraySource` internally.  Any
    other workload passes `source=` (and leaves dataset/clients as None) or
    uses `FLTask.from_source`.
    """

    model: FedModel | Classifier
    dataset: Dataset | None
    clients: list[ClientData] | None
    cluster_members: list[list[int]]  # cluster m -> client ids
    batch_size: int
    seed: int = 0
    source: DataSource | None = None

    def __post_init__(self):
        self.fed_model: FedModel = as_fed_model(self.model)
        if self.source is None:
            assert self.dataset is not None and self.clients is not None, \
                "FLTask needs either (dataset, clients) or an explicit source"
            self.source = ArraySource(
                self.dataset, self.clients, self.batch_size, seed=self.seed
            )
        self.client_sizes = np.asarray(self.source.client_sizes, dtype=np.float64)
        self.cluster_sizes = [
            int(sum(self.client_sizes[i] for i in members)) for members in self.cluster_members
        ]

    @classmethod
    def from_source(cls, model: FedModel, source: DataSource,
                    cluster_members: list[list[int]], *, seed: int = 0) -> FLTask:
        """Build a task directly over a `DataSource` (no array dataset)."""
        return cls(model, None, None, cluster_members, source.batch_size,
                   seed=seed, source=source)

    def reset_loaders(self, seed: int) -> None:
        """Reseed the per-client samplers — every algorithm run calls this so
        same-seed runs are deterministic and runs don't share rng state."""
        self.source.reset(seed)

    @property
    def num_clients(self) -> int:
        return self.source.num_clients

    @property
    def num_clusters(self) -> int:
        return len(self.cluster_members)

    @property
    def metric_name(self) -> str:
        return self.fed_model.metric_name

    @property
    def metric_mode(self) -> str:
        return self.fed_model.metric_mode

    def cluster_weights(self, m: int) -> np.ndarray:
        """gamma_n^m = D_n / D_{A,m} for clients in cluster m."""
        sizes = self.client_sizes[self.cluster_members[m]]
        return (sizes / sizes.sum()).astype(np.float32)

    def global_weights(self) -> np.ndarray:
        """gamma_n = D_n / D_A over all clients (FedAvg weighting)."""
        return (self.client_sizes / self.client_sizes.sum()).astype(np.float32)

    # ---- batch staging (returns jnp batch pytrees) ------------------------

    def sample_cluster_batches(self, m: int, steps: int) -> Batch:
        """Stacked batches for every client of cluster m:
        leaves (steps, n_clients_m, B, ...)."""
        members = self.cluster_members[m]
        steps_np = _stack_batches([
            _stack_batches([self.source.next_batch(i) for i in members])
            for _ in range(steps)
        ])
        return jax.tree.map(jnp.asarray, steps_np)

    def sample_client_batches(self, client: int, steps: int) -> Batch:
        """One client's next `steps` batches: leaves (steps, B, ...)."""
        batch = _stack_batches([self.source.next_batch(client) for _ in range(steps)])
        return jax.tree.map(jnp.asarray, batch)

    def _stage_round_np(self, m: int, total_steps: int, epochs: int) -> Batch:
        """Host-side staging of one round of cluster-m batches as numpy:
        leaves (J, n, E, B, ...). Per-client draw order is identical to
        epochs-sized incremental sampling, so trajectories don't depend on
        prefetch depth."""
        assert total_steps % epochs == 0
        members = self.cluster_members[m]
        flat = _stack_batches([
            _stack_batches([self.source.next_batch(i) for i in members])
            for _ in range(total_steps)
        ])  # leaves (K, n, B, ...)
        J = total_steps // epochs
        return jax.tree.map(
            lambda a: a.reshape(J, epochs, *a.shape[1:]).swapaxes(1, 2), flat
        )

    def sample_round_batches(self, m: int, total_steps: int, epochs: int) -> Batch:
        """Stage one whole round of cluster-m batches, grouped by interaction,
        for the engine's fused scan: leaves (J, n, E, B, ...) with
        J = total_steps // epochs. One host->device transfer per round."""
        return jax.tree.map(jnp.asarray, self._stage_round_np(m, total_steps, epochs))

    def sample_all_cluster_batches(self, total_steps: int, epochs: int) -> Batch:
        """Stage one 3-tier HFL round for EVERY cluster, padded to a uniform
        client width so the engine can vmap over clusters:
        leaves (J, M, n_max, E, B, ...).
        Padded client slots replicate the cluster's first member (their
        updates are masked out downstream — see `padded_cluster_weights`)."""
        n_max = max(len(members) for members in self.cluster_members)
        per_cluster = []
        for m in range(self.num_clusters):
            b = self._stage_round_np(m, total_steps, epochs)  # (J, n_m, E, ...)
            pad = n_max - len(self.cluster_members[m])
            if pad:
                b = jax.tree.map(
                    lambda a: np.concatenate([a, np.repeat(a[:, :1], pad, axis=1)], axis=1),
                    b,
                )
            per_cluster.append(b)
        stacked = jax.tree.map(lambda *leaves: np.stack(leaves, axis=1), *per_cluster)
        return jax.tree.map(jnp.asarray, stacked)

    def padded_cluster_weights(self):
        """(gammas, mask), both (M, n_max): per-cluster client weights padded
        with zeros, and a 1/0 mask of real client slots."""
        n_max = max(len(members) for members in self.cluster_members)
        M = self.num_clusters
        gammas = np.zeros((M, n_max), np.float32)
        mask = np.zeros((M, n_max), np.float32)
        for m in range(M):
            w = self.cluster_weights(m)
            gammas[m, : len(w)] = w
            mask[m, : len(w)] = 1.0
        return jnp.asarray(gammas), jnp.asarray(mask)

    def init_params(self) -> PyTree:
        return self.fed_model.init(jax.random.PRNGKey(self.seed))

    def num_params(self) -> int:
        return tree_num_params(self.init_params())

    def param_leaf_sizes(self) -> tuple[int, ...]:
        """Per-leaf entry counts of the params pytree, in leaf order — what a
        wire channel needs to price a message exactly (packed blocks are laid
        out per leaf, so each leaf rounds up to whole blocks independently)."""
        return tuple(leaf.size for leaf in jax.tree.leaves(self.init_params()))

    def evaluate(self, params: PyTree) -> float:
        """The task's scalar quality metric (accuracy, perplexity, ...)."""
        return self.fed_model.eval_metric(params, self.source.eval_data())


@dataclasses.dataclass
class RunRecorder:
    """The ONE eval/log tail shared by every driver, looped or scanned.

    The four looped drivers used to carry four duplicated copies of the
    cadence check + metric/loss fetch; the scanned executor needs the same
    logic fired at chunk boundaries.  `record(t, params, losses)` appends to
    the logs iff t is an eval round (t % eval_every == 0, or the final
    round); `losses` is the last trained round's on-device loss array (any
    shape — the logged value is `float(jnp.mean(losses))`, the historical
    per-eval host sync) or None when nothing has trained yet (logs NaN, the
    looped drivers' sentinel).

    `obs` (repro.obs.RunTelemetry) is the run's observability carrier: every
    evaluation is wrapped in its "eval" span (the one place eval happens for
    both looped and scanned paths), the loss fetch that follows it in a
    "loss_fetch" span, and the finished telemetry rides out on
    `RunResult.telemetry`.
    """

    task: FLTask
    rounds: int
    eval_every: int
    obs: Any = None
    rounds_log: list = dataclasses.field(default_factory=list)
    acc_log: list = dataclasses.field(default_factory=list)
    loss_log: list = dataclasses.field(default_factory=list)

    def should_eval(self, t: int) -> bool:
        return t % self.eval_every == 0 or t == self.rounds - 1

    def record(self, t: int, params: PyTree, losses) -> None:
        if not self.should_eval(t):
            return
        self.rounds_log.append(t)
        with maybe_span(self.obs, "eval"):
            self.acc_log.append(self.task.evaluate(params))
        with maybe_span(self.obs, "loss_fetch"):
            self.loss_log.append(float("nan") if losses is None else float(jnp.mean(losses)))

    def result(self, name: str, ledger: CommLedger, params: PyTree) -> RunResult:
        return RunResult(name, self.rounds_log, self.acc_log, self.loss_log, ledger,
                         params, metric_mode=self.task.metric_mode,
                         telemetry=self.obs)


@dataclasses.dataclass
class RunResult:
    name: str
    rounds: list[int]
    test_acc: list[float]  # the task metric per eval round (see metric_mode)
    train_loss: list[float]
    ledger: CommLedger
    final_params: PyTree
    metric_mode: str = "max"  # "max": accuracy-like; "min": perplexity-like
    telemetry: Any = None  # repro.obs.RunTelemetry when the run carried one
    sim_times: list | None = None  # simulated wall-clock (s) at each eval —
    #   set by the event-driven async drivers (repro.async_fl), where time is
    #   what the run executes rather than a netsim replay after the fact

    def _empty_metric(self) -> float:
        # an empty log must read as WORST-possible, whatever the metric's
        # direction: 0.0 for accuracy-like metrics, but +inf for
        # perplexity-like ones (0.0 would read as a *perfect* perplexity)
        return 0.0 if self.metric_mode == "max" else float("inf")

    def best_acc(self) -> float:
        if not self.test_acc:
            return self._empty_metric()
        return max(self.test_acc) if self.metric_mode == "max" else min(self.test_acc)

    def final_acc(self) -> float:
        return self.test_acc[-1] if self.test_acc else self._empty_metric()

    def _reached(self, value: float, gamma: float) -> bool:
        return value >= gamma if self.metric_mode == "max" else value <= gamma

    def rounds_to_accuracy(self, gamma: float) -> int | None:
        """First eval round where the metric crosses `gamma` (>= for "max"
        metrics, <= for "min" metrics such as perplexity)."""
        for r, a in zip(self.rounds, self.test_acc):
            if self._reached(a, gamma):
                return r
        return None

    def bits_to_accuracy(self, gamma: float) -> int | None:
        r = self.rounds_to_accuracy(gamma)
        return None if r is None else self.ledger.bits_until(r)

    def sim_time_to_accuracy(self, gamma: float) -> float | None:
        """First simulated wall-clock second at which the metric crosses
        `gamma` — only for runs that carry `sim_times` (async drivers)."""
        if self.sim_times is None:
            return None
        for t_s, a in zip(self.sim_times, self.test_acc):
            if self._reached(a, gamma):
                return t_s
        return None


def evaluate(model: Classifier | FedModel, params: PyTree, eval_data,
             batch: int = 512) -> float:
    """Back-compat scalar evaluation: `model.eval_metric` over `eval_data`
    (for classifiers: test-set accuracy over a `Dataset`, batched at 512)."""
    del batch  # fixed inside ClassifierFedModel.eval_metric
    return as_fed_model(model).eval_metric(params, eval_data)
