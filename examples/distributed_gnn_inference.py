"""Distributed binary-GNN inference: 1-D block-row partition of the FRDC
adjacency + packed-activation all-gather (DESIGN.md §6) — the paper's memory
saving turned into a 32x collective saving at multi-chip scale.

    PYTHONPATH=src python examples/distributed_gnn_inference.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops, frdc
from repro.core.binarize import BinTensor
from repro.core.bspmm import bspmm
from repro.env import enable_compile_cache
from repro.graphs import partition
from repro.graphs.datasets import make_dataset


def main():
    enable_compile_cache()
    d = make_dataset("cora", seed=0, scale=0.3)
    n_shards = 4
    shards = partition.partition_rows(d.edges[0], d.edges[1], d.n_nodes,
                                      n_shards, kind="gcn")
    print("shard balance:", partition.shard_stats(shards))

    rng = np.random.default_rng(0)
    act = rng.choice([-1.0, 1.0], size=(d.n_nodes, 64)).astype(np.float32)
    xt = BinTensor(packed=bitops.pack_bits(act > 0),
                   scale=jnp.ones((d.n_nodes, 1)), n=64)

    # each "chip" aggregates its block-rows from the globally-gathered PACKED
    # activations (the all-gather payload is bits: 64 feats -> 2 words/node)
    outs = []
    for s in shards:
        local = bspmm(s.adj, xt, "BBF")
        outs.append(np.asarray(local)[: s.row_end - s.row_start])
    dist = np.concatenate(outs)[: d.n_nodes]

    full = frdc.gcn_normalized(d.edges[0], d.edges[1], d.n_nodes)
    want = np.asarray(bspmm(full, xt, "BBF"))
    err = np.abs(dist - want).max()
    print(f"distributed == global: max|err| = {err:.2e}")

    packed_payload = d.n_nodes * 2 * 4          # 2 uint32 words / node
    fp_payload = d.n_nodes * 64 * 4
    print(f"all-gather payload: packed {packed_payload/1e3:.1f} KB vs "
          f"fp32 {fp_payload/1e3:.1f} KB ({fp_payload/packed_payload:.0f}x)")


if __name__ == "__main__":
    main()
