"""Paper §3.4 tuning utilities demo: enumerate type-correct precision-variant
assignments for a 2-layer binary GCN, time each on the actual graph, and
report the accuracy/latency frontier.

    PYTHONPATH=src python examples/tune_variants.py
"""
import jax
import jax.numpy as jnp

from repro.core import abstraction, frdc, tuner
from repro.core.bmm import quantize_weight
from repro.env import enable_compile_cache
from repro.graphs.datasets import make_dataset
from repro.models import gnn


def main():
    enable_compile_cache()
    d = make_dataset("cora", seed=0, scale=0.2)
    adj = frdc.gcn_normalized(d.edges[0], d.edges[1], d.n_nodes)
    adj_bin = d.adjacency("binary")
    x = jnp.asarray(d.x)
    params = gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1], 32, d.n_classes)
    q = gnn.quantize_gcn(params)
    reference = gnn.gcn_forward_fp(params, x, frdc.to_dense(adj))

    candidates = tuner.legal_two_layer_candidates(first_in="F", last_out="F")
    print(f"{len(candidates)} type-correct candidates")

    def build(cand: tuner.Candidate):
        (m1, s1), (m2, s2) = cand.layer_variants
        l1 = abstraction.MMSpMM(m1, s1)
        l2 = abstraction.MMSpMM(m2, s2)

        def fwd(x):
            a1 = adj_bin if s1.endswith("BBB") or "BB" in s1 else adj
            h = l1(gnn.batch_norm(x), q.w1, a1,
                   trinary_mode=cand.trinary_mode, out_scale=False)
            if not isinstance(h, jax.Array):
                return l2(h, q.w2, adj)
            return l2(gnn.batch_norm(h), q.w2, adj)
        return fwd

    results = tuner.tune(build, (x,), candidates[:8], reference=reference,
                         repeats=2)
    print(f"{'candidate':70s} {'ms':>8s} {'delta':>8s}")
    for r in results:
        print(f"{r.candidate.name():70s} {r.latency_s*1e3:8.2f} "
              f"{r.output_delta:8.3f}")
    best = tuner.best(results)
    print(f"\nbest: {best.candidate.name()} @ {best.latency_s*1e3:.2f} ms")


if __name__ == "__main__":
    main()
