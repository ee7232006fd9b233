"""Quickstart: convert a GCN into a binary GCN with BitGNN's two-level
abstraction, run packed-bit inference, and inspect the memory saving.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import abstraction, frdc
from repro.core.bmm import quantize_weight
from repro.env import enable_compile_cache
from repro.graphs.datasets import make_dataset
from repro.models import gnn


def main():
    enable_compile_cache()
    # 1. a stat-matched synthetic Cora + a trained(-ish) GCN
    d = make_dataset("cora", seed=0, scale=0.25)
    adj = frdc.gcn_normalized(d.edges[0], d.edges[1], d.n_nodes)   # exact D^-1/2(A+I)D^-1/2
    adj_bin = d.adjacency("binary")                                 # 0/1 bits
    params = gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1], 64, d.n_classes)
    params, loss = gnn.train_node_classifier(
        gnn.gcn_forward_bigcn, params,
        (jnp.asarray(d.x), frdc.to_dense(adj)),
        jnp.asarray(d.y), jnp.asarray(d.train_mask), epochs=150, lr=3e-2)
    print(f"trained Bi-GCN, loss={loss:.3f}")

    # 2. drop-in replacement: high-level fused blocks (paper Fig. 2)
    layer1 = abstraction.MMSpMM("BMM.FBB", "BSpMM.BBB")   # binary aggregation
    layer2 = abstraction.MMSpMM("BMM.BBF", "BSpMM.FBF")   # fp aggregation
    abstraction.check_chain("BMM.FBB", "BSpMM.BBB")        # type-checked

    q = gnn.quantize_gcn(params)   # offline weight bit-packing
    x = jnp.asarray(d.x)
    h = layer1(gnn.batch_norm(x), q.w1, adj_bin, out_scale=False)
    logits = layer2(h, q.w2, adj)
    acc = gnn.accuracy(logits, jnp.asarray(d.y), jnp.asarray(d.test_mask))
    print(f"binary GCN test accuracy: {acc:.3f}")

    # 3. space accounting (paper Tables 3-5 Peak Mem)
    st = frdc.stats(adj_bin)
    print(f"adjacency: FRDC {st['frdc_bytes']/1e3:.1f} KB vs "
          f"CSR-fp32 {st['csr_fp32_bytes']/1e3:.1f} KB "
          f"({st['vs_csr']:.1f}x smaller)")
    w_fp = sum(w.size * 4 for w in params)
    w_bit = sum(int(np.prod(t.packed.shape)) * 4 + t.scale.size * 4
                for t in q)
    print(f"weights: packed {w_bit/1e3:.1f} KB vs fp32 {w_fp/1e3:.1f} KB "
          f"({w_fp/w_bit:.1f}x smaller)")


if __name__ == "__main__":
    main()
