"""Plain Fed-CHS (Algorithm 1 of arXiv:2408.09762): the reference the
benchmark holds the program's rounds to.  It imports nothing of the program.

Round t: the active ES m(t) hands the global model w to its clients and
runs K/E interactions (delta mode): every client starts from w, takes E
local SGD steps, and uploads its change; the ES adds the gamma-weighted sum
of the changes to w.  Under a precision policy clients hold and train bf16
weights (their gradients computed here in float32 from those weights), the
change travels in the wire dtype, and the ES keeps float32.  Then m(t) hands w to the next
ES by the 2-step rule: the least-visited neighbour, ties to the largest
cluster dataset, then to the lowest id.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# the ES chain
# --------------------------------------------------------------------------


def random_sparse(num_nodes: int, max_degree: int = 3, seed: int = 0) -> list:
    """The paper's B.1 topology: a random spanning tree of bounded degree,
    densified by random extra edges under the same cap (the program's
    generator, copied so the benchmark draws the same graph for a seed)."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(num_nodes)
    adj = [set() for _ in range(num_nodes)]
    in_tree = [int(order[0])]
    for u in order[1:]:
        candidates = [v for v in in_tree if len(adj[v]) < max_degree] or in_tree
        v = int(rng.choice(candidates))
        adj[int(u)].add(v)
        adj[v].add(int(u))
        in_tree.append(int(u))
    for _ in range(num_nodes):
        u, v = (int(x) for x in rng.integers(0, num_nodes, size=2))
        if u != v and v not in adj[u] and len(adj[u]) < max_degree \
                and len(adj[v]) < max_degree:
            adj[u].add(v)
            adj[v].add(u)
    return [sorted(a) for a in adj]


def topology(kind: str, num_nodes: int, seed: int) -> list:
    if kind == "random_sparse":
        return random_sparse(num_nodes, 3, seed)
    if kind == "ring":
        return [sorted({(m - 1) % num_nodes, (m + 1) % num_nodes} - {m})
                for m in range(num_nodes)]
    raise ValueError(f"unknown topology {kind!r}")


def visit_order(adjacency: list, cluster_sizes, m0: int, rounds: int) -> list:
    counts = np.zeros(len(adjacency), np.int64)
    counts[m0] = 1
    order = [m0]
    for _ in range(rounds - 1):
        nbrs = adjacency[order[-1]]
        least = min(counts[v] for v in nbrs)
        cands = [v for v in nbrs if counts[v] == least]
        nxt = max(cands, key=lambda v: (cluster_sizes[v], -v))
        counts[nxt] += 1
        order.append(nxt)
    return order


# --------------------------------------------------------------------------
# rounds
# --------------------------------------------------------------------------


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


@jax.jit
def _axpy(acc, gamma, delta):
    return jax.tree.map(lambda a, d: a + gamma * d.astype(jnp.float32), acc, delta)


def _client_step(model, store):
    """One local SGD step of weights held in `store`, its gradient computed
    in float32; the step is taken in float32 and rounded back to `store`."""

    @jax.jit
    def step(p, batch, lr):
        loss, g = model.loss_and_grad(_cast(p, jnp.float32), batch)
        lr_s = jnp.asarray(lr, store).astype(jnp.float32)
        return jax.tree.map(
            lambda w, gi: (w.astype(jnp.float32) - lr_s * gi.astype(jnp.float32)).astype(store),
            p, g), loss

    return step


@jax.jit
def _delta(p_new, p_old):
    return jax.tree.map(lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32))
                        .astype(a.dtype), p_new, p_old)


def _wire(delta, wire):
    if wire is None:
        return delta
    return jax.tree.map(lambda d: d.astype(wire).astype(d.dtype), delta)


def run(model, w0, fed, rounds: int, *, store=None, wire=None, record=(),
        batch_view=None):
    """Train `rounds` Fed-CHS rounds from the float32 weights `w0`.

    `model` gives `loss_and_grad(params, batch)` (computed in the dtype of
    the params it is given) and `metric(params, eval_data)`; `fed` is the
    benchmark's federation (clusters, sizes, schedule, draws).  `store` is
    the dtype clients hold their weights in, `wire` the dtype an uplink
    travels in; None means float32.  Returns, for each round
    in `record`, the host weights after it, the mean of its logged loss row
    and the eval metric.  `batch_view` may rewrite each batch before use."""
    adjacency = topology(fed.topology, fed.num_clusters, fed.topology_seed)
    sizes = [float(fed.client_sizes[c].sum()) for c in fed.clusters]
    order = visit_order(adjacency, sizes, fed.initial_cluster, rounds)
    K, E = fed.local_steps, fed.local_epochs
    store = jnp.dtype(store or jnp.float32)
    draws = [0] * sum(len(c) for c in fed.clusters)
    view = batch_view or (lambda b: b)

    def batches(client, n):
        k0 = draws[client]
        draws[client] += n
        b = fed.source.draw(client, np.arange(k0, k0 + n))
        return [view({k: v[i] for k, v in b.items()}) for i in range(n)]

    w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w0)
    step = _client_step(model, store)
    out = {}
    for t in range(rounds):
        members = fed.clusters[order[t]]
        gammas = fed.gammas(order[t])
        per_client = [batches(c, K) for c in members]
        row = []
        for j in range(K // E):
            acc = jax.tree.map(jnp.zeros_like, w)
            base = _cast(w, store)
            losses = []
            for n, client_batches in enumerate(per_client):
                p, client_losses = base, []
                for e in range(E):
                    p, loss = step(p, client_batches[j * E + e], fed.lrs[j * E + e])
                    client_losses.append(float(loss))
                acc = _axpy(acc, float(gammas[n]), _wire(_delta(p, base), wire))
                losses.append(np.mean(client_losses))
                del p
            w = jax.tree.map(jnp.add, w, acc)
            del acc, base
            row.append(float(np.mean(losses)))
        if t in record:
            out[t] = {"params": jax.device_get(w), "loss": float(np.mean(row)),
                      "metric": float(model.metric(w, fed.source.eval_data()))}
    return out
