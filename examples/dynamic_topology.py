"""Fed-CHS over time-varying networks — the paper's Appendix-D scenarios.

Trains the same non-IID task through three ES networks:
  * static random-sparse graph (the paper's main setting, Appendix B.1),
  * a rotating LEO constellation (the graph shifts every round),
  * an IoV roadside-unit line with flapping links (Gilbert-style drops).

The punchline of §1: the 2-step rule needs no topology assumptions, so
accuracy and communication are essentially unchanged while the network
churns underneath — and there is still zero PS traffic.

  PYTHONPATH=src python examples/dynamic_topology.py
"""
from repro.core import FedCHSConfig, FLTask, run_fed_chs
from repro.data import assign_clusters, dirichlet_partition, make_dataset
from repro.models.classifier import make_classifier


def main():
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ds = make_dataset("mnist", train_size=4000, test_size=1000, seed=0)
    clients = dirichlet_partition(ds.train_y, 20, 0.6, seed=0)
    clusters = assign_clusters(20, 5, seed=0)
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    task = FLTask(model, ds, clients, clusters, batch_size=32, seed=0)

    settings = {
        "static sparse": dict(topology="random_sparse", dynamic=None),
        "LEO rotating": dict(dynamic="leo"),
        "IoV flapping": dict(dynamic="iov"),
    }
    print(f"{'network':14s} {'final_acc':>9s} {'total_MB':>9s} {'ES->ES hops':>12s}")
    for name, kw in settings.items():
        res = run_fed_chs(task, FedCHSConfig(rounds=30, local_steps=10,
                                             eval_every=10, seed=0, **kw))
        print(f"{name:14s} {res.final_acc():9.4f} "
              f"{res.ledger.total_megabytes():9.1f} "
              f"{res.ledger.messages['es_to_es']:12d}")
    print("\nsame accuracy, same bits, one ES->ES hop per round — the 2-step "
          "rule never needed the graph to stand still.")


if __name__ == "__main__":
    main()
