"""The one traffic generator: a mix file of parameters -> a federation.

A mix names the population (clusters and clients), the data each client
holds (Markov token streams over topics, non-IID), the batch, and the
Fed-CHS schedule (local steps, epochs, step size, channel, eval cadence).
One call of `run_fed_chs` runs 1 + eval_every rounds: round 0, then one eval
period, so every chunk after round 0 has the same length; the window
repeats the call.  Everything is drawn from `--seed`: the ES topology and
starting ES, and the data.  Every seed gives the same sizes, so the work
per round does not depend on the seed.

The data is made once, in bulk and on the device, and served to the program
through `SeededSource`, which implements the program's `DataSource`
protocol: draw k of client c is a pure function of (c, k), so the reference
can replay every batch the program was fed.  The generator follows the
program's own (`repro.data.tokens`); it is copied here so that no later
change of the program changes the benchmark's inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed for one use of `--seed` (which may exceed 32 bits)."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, tag]
    return int(np.random.SeedSequence(words).generate_state(1)[0] & 0x7FFFFFFF)


# the uses of `--seed`; a tag keeps its number, so a seed keeps its inputs
TAG_TOPOLOGY, TAG_START, TAG_DATA, TAG_WEIGHTS, TAG_DRAWS, TAG_PROGRAM = 1, 2, 3, 5, 6, 7


class SeededSource:
    """The program's `DataSource` over data the benchmark made.

    `draw(client, ks)` returns the stacked batches of the client's draws
    `ks` (numpy leaves (len(ks), B, ...)); it is a pure function, so the
    same draw index always yields the same rows."""

    def __init__(self, draw: Callable, eval_data: Any, num_clients: int,
                 batch_size: int, client_sizes: np.ndarray):
        self.draw = draw
        self._eval = eval_data
        self.num_clients = num_clients
        self.batch_size = batch_size
        self.client_sizes = np.asarray(client_sizes, np.float64)
        self.reset(0)

    def reset(self, seed: int) -> None:
        del seed  # the data is fixed by the benchmark's seed
        self.draw_counts = [0] * self.num_clients

    def next_batches(self, client: int, count: int):
        k0 = self.draw_counts[client]
        self.draw_counts[client] = k0 + count
        return self.draw(client, np.arange(k0, k0 + count))

    def next_batch(self, client: int):
        return {k: v[0] for k, v in self.next_batches(client, 1).items()}

    def eval_data(self):
        return self._eval


@dataclasses.dataclass
class Federation:
    """One cell's federation, as both the program and the reference see it."""

    clusters: list            # cluster m -> client ids
    client_sizes: np.ndarray  # D_n: gamma_n = D_n / D_{A,m}
    local_steps: int          # K
    local_epochs: int         # E
    lrs: np.ndarray           # (K,) float32 step sizes of one round
    eval_every: int
    rounds: int               # rounds of one call of the driver
    topology: str
    topology_seed: int
    initial_cluster: int
    program_seed: int         # FedCHSConfig.seed
    client_microbatch: int | None
    channel: dict
    source: SeededSource
    batch_shape: dict         # leaf -> (B, ...) of one batch

    @property
    def num_clusters(self) -> int:
        return len(self.clusters)

    def gammas(self, m: int) -> np.ndarray:
        sizes = self.client_sizes[self.clusters[m]]
        return (sizes / sizes.sum()).astype(np.float32)


# --------------------------------------------------------------------------
# population, schedule
# --------------------------------------------------------------------------


def _clusters(pop: dict) -> list:
    n, m = pop["clients_per_cluster"], pop["clusters"]
    return [list(range(i * n, (i + 1) * n)) for i in range(m)]


def step_sizes(lr: dict, K: int) -> np.ndarray:
    """(K,) step sizes of one round."""
    if lr["kind"] == "constant":
        return np.full(K, lr["value"], np.float32)
    raise ValueError(f"unknown step size {lr}")


# --------------------------------------------------------------------------
# data: Markov token streams
# --------------------------------------------------------------------------


def _token_pool(key, vocab, topics, branch, topic_of_row, length):
    """Walk topic Markov chains on the device: (rows,) topics -> (rows, length)."""
    import jax
    import jax.numpy as jnp

    k_tab, k_start, k_walk = jax.random.split(key, 3)
    succ = jax.random.randint(k_tab, (topics, vocab, branch), 0, vocab, jnp.int32)
    rows = topic_of_row.shape[0]
    start = jax.random.randint(k_start, (rows,), 0, vocab, jnp.int32)
    choice = jax.random.randint(k_walk, (length - 1, rows), 0, branch, jnp.int32)

    def step(tok, c):
        nxt = succ[topic_of_row, tok, c]
        return nxt, nxt

    _, walk = jax.lax.scan(step, start, choice)
    return jnp.concatenate([start[None], walk], axis=0).T


def _tokens(data: dict, vocab: int, clusters: list, draws: int, seed: int):
    import jax
    import jax.numpy as jnp

    n_clients = sum(len(c) for c in clusters)
    B, T, topics = data["batch"], data["seq"], data["topics"]
    rng = np.random.default_rng(sub_seed(seed, TAG_DATA))
    # client n's rows carry its dominant topic n % topics with probability
    # `dominance`, the others share the rest
    off = (1.0 - data["dominance"]) / max(topics - 1, 1)
    probs = np.full((n_clients, topics), off)
    probs[np.arange(n_clients), np.arange(n_clients) % topics] = data["dominance"]
    topic = np.stack([rng.choice(topics, size=draws * B, p=probs[c])
                      for c in range(n_clients)])                 # (n, draws*B)
    eval_topic = rng.integers(0, topics, size=data["eval_batches"] * B)
    rows = np.concatenate([topic.reshape(-1), eval_topic]).astype(np.int32)
    key = jax.random.PRNGKey(sub_seed(seed, TAG_DRAWS))
    walk = jax.jit(_token_pool, static_argnums=(1, 2, 3, 5))(
        key, vocab, topics, data["branch"], jnp.asarray(rows), T + 1)
    walk = np.asarray(walk)
    pool = walk[: n_clients * draws * B].reshape(n_clients, draws, B, T + 1)
    ev = walk[n_clients * draws * B:].reshape(data["eval_batches"], B, T + 1)

    def draw(client, ks):
        toks = pool[client, ks % draws]
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    eval_data = {"tokens": ev[..., :-1], "labels": ev[..., 1:]}
    return draw, eval_data, np.ones(n_clients), {"tokens": (B, T), "labels": (B, T)}


# --------------------------------------------------------------------------
# the federation of one cell and seed
# --------------------------------------------------------------------------


def build(mix: dict, config: dict, seed: int) -> Federation:
    fed = mix["federation"]
    clusters = _clusters(mix["population"])
    K, E = fed["local_steps"], fed["local_epochs"]
    rounds = 1 + fed["eval_every"]
    data = mix["data"]
    if data["kind"] != "tokens":
        raise ValueError(f"unknown data kind {data['kind']!r}")
    # every row a client draws in one call differs: a client trains at most
    # K steps a round
    draw, eval_data, sizes, shapes = _tokens(
        data, config["vocab_size"], clusters, rounds * K, seed)
    n_clients = sum(len(c) for c in clusters)
    source = SeededSource(draw, eval_data, n_clients, data["batch"], sizes)
    rng = np.random.default_rng(sub_seed(seed, TAG_START))
    return Federation(
        clusters=clusters, client_sizes=sizes, local_steps=K, local_epochs=E,
        lrs=step_sizes(fed["lr"], K), eval_every=fed["eval_every"], rounds=rounds,
        topology=fed["topology"], topology_seed=sub_seed(seed, TAG_TOPOLOGY),
        initial_cluster=int(rng.integers(len(clusters))),
        program_seed=sub_seed(seed, TAG_PROGRAM),
        client_microbatch=fed.get("client_microbatch"), channel=fed["channel"],
        source=source, batch_shape=shapes)
