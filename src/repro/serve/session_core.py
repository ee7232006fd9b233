"""Shared compile/calibrate/serve machinery of the GNN serving sessions.

This module is the seam between the single-host :class:`CompiledGraphSession`
(:mod:`repro.serve.gnn_session`) and the partitioned
:class:`ShardedGraphSession` (:mod:`repro.serve.sharded`): everything that is
NOT about who owns the graph lives here —

  * :class:`SessionPlan` + the tuner-driven plan selection (paper §3.4);
  * family-dispatched bitgnn forwards (optionally routed through the Pallas
    kernels, see :func:`family_forward`);
  * :class:`ServeCore`, the bucket-shaped jitted subgraph forward with the
    HIGH-WATER pow2 shape buckets and the jit trace counter (the
    zero-steady-state-recompiles verification counter);
  * subgraph FRDC construction carrying FULL-graph factorization vectors, so
    a k-hop forward reproduces the full-graph computation for the seed rows
    exactly — on one host or on the seed's owning shard;
  * FRDC array (de)serialization helpers shared by both artifact formats.
  * the :class:`LayerExecutor` seam of the DISTRIBUTED full pass: a family
    forward is decomposed into :class:`LayerStep`\\ s (BN site -> per-shard
    transform -> halo exchange -> aggregation -> combine) by
    :func:`build_layer_program`; an executor (host-orchestrated or SPMD,
    :mod:`repro.serve.sharded.executor`) runs the same program either as
    eager per-shard stages or as one ``shard_map`` program per layer.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops, frdc, tuner
from repro.core.binarize import BinTensor
from repro.core.bmm import bmm, quantize_act
from repro.core.bspmm import TRINARY_DEFAULT
from repro.kernels import ops as kernel_ops
from repro.models import gnn

FAMILIES = ("gcn", "sage", "saint")

# layer_variants of the two legal GCN end-to-end schemes (paper Table 3);
# SAGE/SAINT run the fixed Fig. 2 pipeline (BMM.BBF branches + BSpMM.FBF).
GCN_SCHEME_VARIANTS = {
    "full": (("BMM.BBF", "BSpMM.FBF"), ("BMM.BBF", "BSpMM.FBF")),
    "bin": (("BMM.FBB", "BSpMM.BBB"), ("BMM.BBF", "BSpMM.FBF")),
}
FIXED_VARIANTS = (("BMM.BBF", "BSpMM.FBF"), ("BMM.BBF", "BSpMM.FBF"))

# adjacency kinds each family's packed forward consumes
FAMILY_ADJ_KINDS = {"gcn": ("adj", "bin"), "sage": ("mean",), "saint": ("sum",)}

# number of aggregation layers per family: the k of the k-hop closure a
# served node needs, and the hop count of the out-neighborhood a feature
# update invalidates.
FAMILY_AGG_LAYERS = {"gcn": 2, "sage": 2, "saint": 2}


def bucket_pow2(n: int, floor: int, cap: Optional[int] = None) -> int:
    """Round up to the power-of-two bucket grid (>= floor, <= cap)."""
    b = floor
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


@dataclasses.dataclass
class SessionPlan:
    """Tuner-selected execution plan of one compiled session.

    ``bspmm_block`` is the Pallas BSpMM block-shape tunable — ``(rows,
    feats)`` of one kernel grid step's output block, or None for the
    kernel-native defaults (one FRDC tile-row of ``frdc.TILE`` rows x the
    full feature width). It rides in ``plan.json`` with the rest of the
    plan, so a TPU block-shape sweep (ROADMAP open item) records its winner
    in the same artifact the tuner's variant choice lives in.

    ``fused`` selects the fused per-layer kernel path: each GNN layer —
    BN, binary transform, BSpMM aggregation, combine/activation — compiles
    to ONE Pallas launch (:mod:`repro.kernels.fused_layer`). Bitwise
    identical to the unfused path; only effective where the kernels are
    active (``use_pallas`` on TPU / ``force_kernels``), and calibration
    passes (which must RECORD bn stats) always run unfused.
    """
    family: str
    scheme: str                       # gcn: "full" | "bin"; else "fixed"
    trinary_mode: str = TRINARY_DEFAULT
    layer_variants: tuple = FIXED_VARIANTS
    tuned_latency_s: float = float("nan")
    output_delta: float = float("nan")
    bspmm_block: Optional[Tuple[int, int]] = None
    fused: bool = False

    def name(self) -> str:
        layers = ";".join(f"{m}+{s}" for m, s in self.layer_variants)
        blk = ("" if self.bspmm_block is None
               else f"|blk{self.bspmm_block[0]}x{self.bspmm_block[1]}")
        fz = "|fused" if self.fused else ""
        return f"{self.family}/{self.scheme}[{layers}|{self.trinary_mode}" \
               f"{blk}{fz}]"

    def to_json(self) -> dict:
        return dict(family=self.family, scheme=self.scheme,
                    trinary_mode=self.trinary_mode,
                    layer_variants=[list(v) for v in self.layer_variants],
                    tuned_latency_s=self.tuned_latency_s,
                    output_delta=self.output_delta,
                    bspmm_block=(None if self.bspmm_block is None
                                 else list(self.bspmm_block)),
                    fused=self.fused)

    @classmethod
    def from_json(cls, d: dict) -> "SessionPlan":
        blk = d.get("bspmm_block")
        return cls(family=d["family"], scheme=d["scheme"],
                   trinary_mode=d["trinary_mode"],
                   layer_variants=tuple(tuple(v) for v in d["layer_variants"]),
                   tuned_latency_s=d.get("tuned_latency_s", float("nan")),
                   output_delta=d.get("output_delta", float("nan")),
                   bspmm_block=None if blk is None else tuple(blk),
                   fused=bool(d.get("fused", False)))


def quantize_family(family: str, params):
    return {"gcn": gnn.quantize_gcn, "sage": gnn.quantize_sage,
            "saint": gnn.quantize_saint}[family](params)


def family_forward(plan: SessionPlan, qparams, x,
                   adjs: Dict[str, frdc.FRDCMatrix],
                   use_pallas: bool = False, **kw):
    """Dispatch the family's packed forward under ``plan``.

    ``use_pallas`` routes the BSpMM aggregations through the Pallas kernels
    (:func:`repro.kernels.ops.serve_kernels`) — native on TPU, and a no-op
    fallback to the reference jnp path off-TPU. The flag is consulted at jit
    TRACE time, so a session built with it bakes the kernel calls into its
    compiled executables. ``plan.bspmm_block`` rides along as the kernels'
    block-shape selection.
    """
    fused = (plan.fused and kernel_ops.kernels_active(use_pallas)
             and kw.get("bn_stats") is not None
             and not kw.get("return_bn_stats", False))
    if fused:
        return _fused_family_forward(plan, qparams, x, adjs,
                                     kw["bn_stats"])
    with kernel_ops.serve_kernels(use_pallas, block_shape=plan.bspmm_block):
        if plan.family == "gcn":
            return gnn.gcn_forward_bitgnn(
                qparams, x, adjs["adj"], adjs["bin"], scheme=plan.scheme,
                trinary_mode=plan.trinary_mode, **kw)
        if plan.family == "sage":
            return gnn.sage_forward_bitgnn(qparams, x, adjs["mean"], **kw)
        return gnn.saint_forward_bitgnn(qparams, x, adjs["sum"], **kw)


def _fused_family_forward(plan: SessionPlan, qparams, x,
                          adjs: Dict[str, frdc.FRDCMatrix],
                          bn_stats: tuple):
    """Serve the forward as ONE Pallas kernel per layer.

    Each layer callable from :func:`repro.models.gnn.bitgnn_layers` is
    traced inside a single ``fused_layer.fused_call`` launch with the
    VALUE-level aggregation backends installed (``serve_kernels(fused=
    True)``) — BN, transform, aggregation and activation all land in one
    kernel body. Traced values (activations, bn stats, FRDC fields) enter
    as kernel operands; concrete weights ride in the layer closures. The
    inter-layer carry is ARRAY-only: a binary carry (gcn "bin" layer 1,
    ``out_scale=False`` => unit scales) crosses the boundary as its packed
    words and is re-wrapped inside the next body — ``BinTensor.n`` must
    stay a python int, which a kernel boundary would not preserve.

    Bitwise identical to the unfused path: the value walks accumulate in
    kernel order, and the BN-site cursor threads across layers at trace
    time exactly as the monolithic forward's ``_BNTap`` does.
    """
    from repro.kernels import fused_layer

    layers = gnn.bitgnn_layers(plan.family, qparams, plan.scheme,
                               plan.trinary_mode)
    key = {"gcn": None, "sage": "mean", "saint": "sum"}[plan.family]
    mats_src = adjs if key is None else {"adj": adjs[key]}
    arrs = {k: frdc_arrays(m) for k, m in mats_src.items()}

    h = x
    site = 0
    bin_n = None
    meta: dict = {}
    with kernel_ops.serve_kernels(True, block_shape=plan.bspmm_block,
                                  fused=True):
        for fn in layers:
            def body(h_in, stats, ar, fn=fn, start=site, bin_n=bin_n):
                mats = {k: frdc_rebuild(ar[k], mats_src[k].n_rows,
                                        mats_src[k].n_cols, mats_src[k].nnz)
                        for k in ar}
                tap = gnn._BNTap(stats)
                tap._i = start
                hh = h_in
                if bin_n is not None:
                    hh = BinTensor(
                        packed=h_in,
                        scale=jnp.ones((h_in.shape[0], 1), jnp.float32),
                        n=bin_n)
                out = fn(tap, hh, mats)
                meta["site"] = tap._i
                if isinstance(out, BinTensor):
                    meta["bin_n"] = out.n
                    return out.packed
                meta["bin_n"] = None
                return out

            h = fused_layer.fused_call(body, h, bn_stats, arrs)
            site, bin_n = meta["site"], meta["bin_n"]
    return h


# ---------------------------------------------------------------------------
# FRDC array (de)serialization — shared by both artifact formats
# ---------------------------------------------------------------------------

def frdc_arrays(m: frdc.FRDCMatrix) -> dict:
    out = dict(tiles=m.tiles, col_idx=m.col_idx, group_row=m.group_row,
               group_first=m.group_first, grp_ptr=m.grp_ptr)
    if m.row_scale is not None:
        out["row_scale"] = m.row_scale
    if m.col_scale is not None:
        out["col_scale"] = m.col_scale
    return out


def frdc_rebuild(arrs: dict, n_rows: int, n_cols: int,
                 nnz: int = 0) -> frdc.FRDCMatrix:
    return frdc.FRDCMatrix(
        tiles=arrs["tiles"], col_idx=arrs["col_idx"],
        group_row=arrs["group_row"], group_first=arrs["group_first"],
        grp_ptr=arrs["grp_ptr"], n_rows=int(n_rows), n_cols=int(n_cols),
        nnz=int(nnz), row_scale=arrs.get("row_scale"),
        col_scale=arrs.get("col_scale"))


# FRDC array fields per adjacency kind of each family — the (deterministic)
# pytree structure of a saved artifact, so load() can build the restore
# template without encoding any adjacency.
FRDC_BASE_FIELDS = ("tiles", "col_idx", "group_row", "group_first", "grp_ptr")
ADJ_SCALE_FIELDS = {
    "gcn": {"adj": ("row_scale", "col_scale"), "bin": ()},
    "sage": {"mean": ("row_scale",)},
    "saint": {"sum": ()},
}


def adj_like(family: str) -> dict:
    return {kind: {f: np.zeros(0) for f in FRDC_BASE_FIELDS + extra}
            for kind, extra in ADJ_SCALE_FIELDS[family].items()}


def coerce_quant(q):
    """Re-type a checkpoint-restored quantized param tree: the static ``n``
    field of each BinTensor round-trips through npz as a 0-d array and must
    come back as a python int (it participates in jit-static shape logic)."""
    from repro.core.binarize import BinTensor
    return type(q)(*(BinTensor(packed=jnp.asarray(t.packed),
                               scale=jnp.asarray(t.scale), n=int(t.n))
                     for t in q))


def feature_fingerprint(x: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(x).tobytes()).hexdigest()[:16]


def session_fingerprint(graph, model) -> dict:
    """Identity of a (graph, model) pair a serving artifact was compiled
    for — THE match key of every artifact restore path (single-host
    ``plan.json`` and sharded ``routing.json`` alike), so it lives here
    once. ``graph``/``model`` are the store's registry entries."""
    d = graph.data
    return dict(graph=graph.name, model=model.name, family=model.family,
                n_nodes=int(d.n_nodes), n_edges=int(d.n_edges),
                features=feature_fingerprint(d.x))


# ---------------------------------------------------------------------------
# Artifact robustness — typed corruption errors for both restore paths
# ---------------------------------------------------------------------------

class ArtifactError(RuntimeError):
    """A serving artifact on disk is CORRUPT (truncated sidecar, unparsable
    JSON, a half-written npz) — as opposed to merely missing or mismatched,
    which the load paths report by returning None so the caller recompiles.
    Corruption must not silently recompile (the artifact the operator
    deployed is broken and someone should know) and must not surface as a
    raw JSONDecodeError/BadZipFile traceback either; it names the file and
    the field that failed."""

    def __init__(self, path, field: str = "", detail: str = ""):
        self.path = str(path)
        self.field = field
        self.detail = detail
        msg = f"corrupt serving artifact {self.path}"
        if field:
            msg += f" (field {field!r})"
        if detail:
            msg += f": {detail}"
        super().__init__(msg)


def load_sidecar(path, required: Tuple[str, ...] = ()) -> Optional[dict]:
    """Read an artifact sidecar (``plan.json`` / ``routing.json``). Missing
    file -> None (no artifact: recompile). Unparsable JSON, a non-object
    payload, or a missing required field -> :class:`ArtifactError` naming
    the file and field."""
    import json
    import pathlib
    path = pathlib.Path(path)
    if not path.exists():
        return None
    try:
        sidecar = json.loads(path.read_text())
    except (ValueError, OSError) as e:
        raise ArtifactError(path, field="json", detail=str(e))
    if not isinstance(sidecar, dict):
        raise ArtifactError(path, field="json",
                            detail=f"expected an object, got "
                                   f"{type(sidecar).__name__}")
    for f in required:
        if f not in sidecar:
            raise ArtifactError(path, field=f, detail="missing field")
    return sidecar


def restore_artifact_state(directory, like):
    """Checkpointer restore with typed corruption reporting: None when no
    complete checkpoint exists or its pytree structure mismatches ``like``
    (recompile), :class:`ArtifactError` when the manifest or npz payload is
    present but unreadable (truncated write, bad zip, missing leaves)."""
    import json
    import pathlib
    import zipfile
    from repro.checkpoint.checkpointer import Checkpointer, _flatten
    ckpt = Checkpointer(directory, keep=1)
    step = ckpt.latest_step()
    if step is None:
        return None
    out = pathlib.Path(directory) / f"step_{step:08d}"
    man_path = out / "manifest.json"
    try:
        manifest = json.loads(man_path.read_text())
    except (ValueError, OSError) as e:
        raise ArtifactError(man_path, field="json", detail=str(e))
    for f in ("keys", "n_leaves", "shards"):
        if f not in manifest:
            raise ArtifactError(man_path, field=f, detail="missing field")
    keys, _, treedef = _flatten(like)
    if keys != manifest["keys"]:
        return None                    # structure mismatch: recompile
    npz_path = out / manifest["shards"][0]
    if not npz_path.exists():
        raise ArtifactError(npz_path, field="shards",
                            detail="manifest names a missing shard file")
    try:
        data = np.load(npz_path)
        leaves = [jnp.asarray(data[f"a{i}"])
                  for i in range(int(manifest["n_leaves"]))]
    except (zipfile.BadZipFile, KeyError, ValueError, OSError) as e:
        raise ArtifactError(npz_path, field="leaves", detail=str(e))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Subgraph adjacency construction (full-graph factorization vectors)
# ---------------------------------------------------------------------------

def sub_adjacency(family: str, n_sub: int, sub_edges: np.ndarray,
                  dinv_sub: Optional[np.ndarray]
                  ) -> Dict[str, frdc.FRDCMatrix]:
    """Per-family subgraph FRDC matrices. ``dinv_sub`` is the FULL-graph
    factorization vector gathered at the subgraph's nodes (GCN: D^-1/2 with
    self-loops; SAGE: D^-1 mean; SAINT: None) so seed-row aggregation is
    identical to the full graph no matter which host gathered it.

    Built NUMPY-backed (``device=False``): this sits in the serving
    pipeline's extract stage, which must stay pure host work — the jit call
    boundary converts the staged arrays at launch."""
    if family == "gcn":
        loops = np.arange(n_sub, dtype=np.int64)
        r = np.concatenate([sub_edges[0], loops])
        c = np.concatenate([sub_edges[1], loops])
        return {
            "adj": frdc.from_coo(r, c, n_sub, n_sub, row_scale=dinv_sub,
                                 col_scale=dinv_sub, device=False),
            "bin": frdc.from_coo(sub_edges[0], sub_edges[1], n_sub, n_sub,
                                 device=False),
        }
    if family == "sage":
        return {"mean": frdc.from_coo(sub_edges[0], sub_edges[1], n_sub,
                                      n_sub, row_scale=dinv_sub,
                                      device=False)}
    return {"sum": frdc.from_coo(sub_edges[0], sub_edges[1], n_sub, n_sub,
                                 device=False)}


def dinv_for_family(family: str, degrees: np.ndarray) -> Optional[np.ndarray]:
    """Full-graph factorization vector from full-graph receiver degrees."""
    if family == "gcn":
        return 1.0 / np.sqrt(degrees + 1.0)          # self-loops included
    if family == "sage":
        return 1.0 / np.maximum(degrees.astype(np.float64), 1.0)
    return None


# ---------------------------------------------------------------------------
# Layer programs — the distributed full pass decomposed into executor steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerStep:
    """One step of a family's distributed layer program.

    A step runs per shard as: optional BN (site ``bn_site`` of the frozen
    calibration tuple — or, in calibrate mode, distributed moments computed
    across shards) -> ``pre`` (dense per-shard transform producing the
    exchange operand + any aux state ``post`` needs) -> halo exchange of the
    operand (bit-packed uint32 words when ``packed``) -> aggregation
    ``intra @ operand + halo @ exchanged`` over adjacency ``kind`` (trinary
    popc counts when ``packed`` — integer partial sums, exact across the
    split) -> ``post(aux, y)`` producing the next carried state. Steps with
    ``kind=None`` skip the exchange/aggregation and feed ``pre``'s operand
    straight to ``post`` (SAINT's trailing FC).

    The carried state between steps is a single array (fp activations, or
    the packed uint32 words of the GCN "bin" scheme's binarized hidden
    layer) so it crosses SPMD program boundaries without pytree gymnastics.

    ``payload_cols``/``payload_itemsize`` describe the exchange operand's
    static row width — the wire-byte schedule of the step
    (``MeshHaloPlan.payload_bytes``), recorded by the executors OUTSIDE any
    trace so jitted steady-state passes account correctly.
    """
    name: str
    kind: Optional[str]
    packed: bool
    bn_site: Optional[int]
    pre: Callable
    post: Callable
    payload_cols: int = 0
    payload_itemsize: int = 4

    @property
    def tag(self) -> str:
        """Halo byte-accounting tag (stable across PR 2's benchmark keys)."""
        return f"{self.name}/{'packed' if self.packed else 'fp'}"


def binarize_counts(counts: jax.Array, n_feat: int) -> BinTensor:
    """Sign-binarize summed trinary counts — the BSpMM.BBB output stage
    (``out_scale=False``: positive scales are elided by the consumer)."""
    counts = counts.astype(jnp.float32)
    if counts.shape[-1] > n_feat:
        counts = counts[:, :n_feat]
    return BinTensor(packed=bitops.sign_bits(counts, axis=-1),
                     scale=jnp.ones((counts.shape[0], 1), counts.dtype),
                     n=n_feat)


def build_layer_program(plan: SessionPlan, q) -> Tuple[LayerStep, ...]:
    """Decompose ``plan``'s family forward into executor layer steps.

    Executing the program per shard with the single-host BN constants is
    arithmetically IDENTICAL to the family's ``*_forward_bitgnn`` over the
    whole graph wherever the aggregation split is exact (binary layers) and
    fp-reassociation-close elsewhere — the same invariant the PR 2
    host-orchestrated pass relied on, now stated once and shared by both
    executors.
    """
    fam = plan.family
    if fam == "gcn" and plan.scheme == "bin":
        n_hidden = int(q.w1.packed.shape[0])
        n_out = int(q.w2.packed.shape[0])

        def pre1(z):
            hb = bmm(z, q.w1, "FBB", out_scale=False)
            return hb.packed, None

        def post1(aux, counts):
            return binarize_counts(counts, n_hidden).packed

        def pre2(st):
            h1 = BinTensor(packed=st,
                           scale=jnp.ones((st.shape[0], 1), jnp.float32),
                           n=n_hidden)
            return bmm(h1, q.w2, "BBF"), None

        return (
            LayerStep("layer1", "bin", True, 0, pre1, post1,
                      payload_cols=-(-n_hidden // 32)),
            LayerStep("layer2", "adj", False, None, pre2,
                      lambda aux, y: y, payload_cols=n_out),
        )
    if fam == "gcn":
        n_hidden = int(q.w1.packed.shape[0])
        n_out = int(q.w2.packed.shape[0])

        def pre_l(w):
            def pre(z):
                return bmm(quantize_act(z), w, "BBF"), None
            return pre

        return (
            LayerStep("layer1", "adj", False, 0, pre_l(q.w1),
                      lambda aux, y: jax.nn.relu(y), payload_cols=n_hidden),
            LayerStep("layer2", "adj", False, 1, pre_l(q.w2),
                      lambda aux, y: y, payload_cols=n_out),
        )

    # sage / saint: self + aggregated branch merged by ADD per layer
    kind = "mean" if fam == "sage" else "sum"

    def branch_pre(w_agg):
        def pre(z):
            xq = quantize_act(z)
            return bmm(xq, w_agg, "BBF"), xq
        return pre

    def branch_post(w_self, relu):
        def post(xq, agg):
            h = bmm(xq, w_self, "BBF") + agg
            return jax.nn.relu(h) if relu else h
        return post

    steps = [
        LayerStep("layer1", kind, False, 0, branch_pre(q.w1_agg),
                  branch_post(q.w1_self, True),
                  payload_cols=int(q.w1_agg.packed.shape[0])),
        LayerStep("layer2", kind, False, 1, branch_pre(q.w2_agg),
                  branch_post(q.w2_self, fam == "saint"),
                  payload_cols=int(q.w2_agg.packed.shape[0])),
    ]
    if fam == "saint":
        steps.append(LayerStep(
            "fc", None, False, 2,
            lambda z: (bmm(quantize_act(z), q.w_fc, "BBF"), None),
            lambda aux, y: y))
    return tuple(steps)


def apply_bn(x: jax.Array, mu: jax.Array, sd: jax.Array) -> jax.Array:
    """Frozen-stats batch norm in the executors' bit-stable form.

    XLA CPU compiles an EAGER broadcast division ``x / sd`` and the same
    division inside a jitted program to differently-rounded code (~1 ulp),
    which would break host-vs-SPMD bit-exactness at every BN site; the
    multiply-by-reciprocal form is bit-stable across both, so both layer
    executors normalize through this helper."""
    return (x - mu) * (1.0 / sd)


# the eps of gnn.bn_stats — shared by BOTH distributed-calibration
# implementations (host partial sums below, SPMD psum moments in
# serve/sharded/executor.py) so the two formulas cannot silently diverge.
BN_EPS = 1e-5


def moments_from_sums(s1, s2, cnt, eps: float = BN_EPS) -> tuple:
    """(mu, sd) from sum / sum-of-squares / count partials — THE formula of
    distributed BN calibration, shared by the host executor (python-summed
    partials) and the SPMD executor (psum-combined partials)."""
    mu = s1 / cnt
    sd = jnp.sqrt(jnp.maximum(s2 / cnt - mu * mu, 0.0)) + eps
    return mu, sd


def distributed_moments(blocks: List[jax.Array],
                        eps: float = BN_EPS) -> tuple:
    """Per-feature (mu, sd) over the GLOBAL node axis from per-shard row
    blocks — the host-side twin of the SPMD executor's psum moments (sum /
    sum-of-squares partials combined across shards), so both executors'
    "distributed" BN calibrations agree to reduction-order tolerance."""
    cnt = float(sum(int(b.shape[0]) for b in blocks))
    s1 = sum(jnp.sum(b, axis=0, keepdims=True) for b in blocks)
    s2 = sum(jnp.sum(b * b, axis=0, keepdims=True) for b in blocks)
    return moments_from_sums(s1, s2, cnt, eps)


class LayerExecutor:
    """Executes a layer program over per-shard feature blocks.

    ``run_pass(program, xs, bn, calibrate=False)`` takes the per-shard
    UNPADDED feature blocks and either the frozen BN tuple (site-indexed) or
    ``calibrate=True`` to compute the stats from the pass itself; returns
    ``(per-shard output blocks, collected stats or None)``. Implementations:
    :class:`repro.serve.sharded.executor.HostLayerExecutor` (eager per-shard
    stages, PR 2 semantics — the bit-exactness reference) and
    :class:`repro.serve.sharded.executor.SpmdLayerExecutor` (one
    ``shard_map`` program per layer, fused halo exchange, psum BN moments).
    """
    name = "?"

    @property
    def compile_count(self) -> int:
        """Traces of the executor's jitted layer programs — constant after
        the first pass (zero steady-state recompiles)."""
        return 0

    def run_pass(self, program, xs, bn, calibrate: bool = False):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# ServeCore — the bucket-shaped jitted subgraph forward
# ---------------------------------------------------------------------------

class ServeCore:
    """One jitted bucketed forward + its high-water shape buckets.

    The core owns the family-AGNOSTIC serving machinery: the jit cache and
    its trace counter, the high-water pow2 buckets, async launch/finish,
    and multi-bucket co-launch. What a launch actually computes is the
    ``adapter``'s business (:class:`repro.serve.adapters.ModelFamilyAdapter`
    — quantize, traced body, operand padding, result crop); when no adapter
    is given a :class:`~repro.serve.adapters.GNNAdapter` is built from the
    plan, which keeps every pre-existing call site bitwise unchanged.

    For the GNN adapter: node and FRDC group counts are padded up to pow2
    marks that only ever grow (capped at ``node_cap``), so the jitted
    forward converges to one steady padded shape after a short warmup and
    never recompiles in steady state. ``compile_count`` counts jit traces
    (python side effect on trace) and IS the verification counter. Both the
    single-host session and every shard of a sharded session own exactly
    one of these; a token session owns one running its chunked decode.
    """

    NODE_BUCKET_FLOOR = 64
    GROUP_BUCKET_FLOOR = 16

    def __init__(self, plan: SessionPlan, qparams, max_batch: int,
                 node_cap: int, use_pallas: bool = False, adapter=None):
        if adapter is None:
            from .adapters import GNNAdapter
            adapter = GNNAdapter(plan)
        self.adapter = adapter
        self.plan = plan
        self.qparams = qparams
        self.max_batch = max_batch
        self.node_cap = node_cap
        self.use_pallas = use_pallas
        self._n_traces = 0
        # observability hook: called as on_trace(shape_key_dict) whenever a
        # launch triggers a NEW jit trace (the engines' recompile watchdog
        # wires this via the sessions' set_trace_hook)
        self.on_trace = None
        # high-water shape buckets: node and group pads only ever GROW (in
        # pow2 steps, capped at node_cap), so serving stops recompiling —
        # warmup is a handful of max-width batches, not a shape sweep.
        self._n_water = 0
        self._g_water: Dict[Tuple[int, str], int] = {}
        self._jit_serve = jax.jit(self._serve)
        self._jit_serve_many = jax.jit(self._serve_many)
        # device dispatches issued (a launch_many of K buckets counts 1) —
        # the launches-per-tick regression metric benches and tests key on
        self.n_dispatches = 0

    @property
    def compile_count(self) -> int:
        return self._n_traces

    def _serve(self, x, bn, adjs, seeds):
        self._n_traces += 1
        return self._serve_one(x, bn, adjs, seeds)

    def _serve_one(self, x, bn, adjs, seeds):
        return self.adapter.serve_body(self, x, bn, adjs, seeds)

    def _serve_many(self, batches):
        """K bucketed forwards UNROLLED into one jitted program (one device
        dispatch serves K staged buckets). Unrolled — not vmapped — so each
        batch's sub-jaxpr is exactly ``_serve_one``'s and the K outputs stay
        bitwise identical to K serial launches; buckets of different padded
        shapes (and different captured ``bn``) co-launch freely. One program
        trace counts as ONE compile regardless of K."""
        self._n_traces += 1
        return tuple(self._serve_one(x, bn, adjs, seeds)
                     for (x, bn, adjs, seeds) in batches)

    def _pad_mats(self, mats: Dict[str, frdc.FRDCMatrix], n_sub: int):
        return self.adapter.pad_operands(self, mats, n_sub)

    def stage(self, x_sub: np.ndarray, mats: Dict[str, frdc.FRDCMatrix],
              seed_pos: np.ndarray) -> "StagedBatch":
        """EXTRACT-stage tail: bucket-pad one extracted subgraph into the
        launch-ready host arrays. Pure host work (the water-mark update
        happens here, so staging order — not launch order — is what the
        zero-recompile guarantee keys on)."""
        n_pad, adjs = self._pad_mats(mats, x_sub.shape[0])
        x_pad = np.zeros((n_pad, x_sub.shape[1]), np.float32)
        x_pad[:x_sub.shape[0]] = x_sub
        pos_pad = np.zeros((self.max_batch,), np.int32)
        pos_pad[:seed_pos.size] = seed_pos
        return StagedBatch(x_pad=x_pad, adjs=adjs, pos_pad=pos_pad,
                           n_seeds=int(seed_pos.size))

    def launch(self, staged: "StagedBatch", bn: tuple) -> jax.Array:
        """COMPUTE-stage head: dispatch the jitted bucketed forward. Under
        jax's async dispatch this returns before the device finishes, so the
        caller can overlap the next batch's extraction with it."""
        c0 = self._n_traces
        self.n_dispatches += 1
        out = self._jit_serve(jnp.asarray(staged.x_pad), bn, staged.adjs,
                              jnp.asarray(staged.pos_pad))
        if self._n_traces > c0 and self.on_trace is not None:
            # a NEW trace: report the offending shape key (the padded dims
            # that define the jit cache entry)
            self.on_trace(self.adapter.trace_shape(staged))
        return out

    def launch_many(self, entries: List[Tuple["StagedBatch", tuple]]
                    ) -> List[jax.Array]:
        """Dispatch SEVERAL staged buckets as one jitted program (one device
        dispatch, K results). ``entries``: (staged, bn) pairs — each bucket
        launches under its own captured calibration. Bitwise identical to K
        serial :meth:`launch` calls (the program is the K ``_serve_one``
        bodies unrolled); the jit cache keys on the (K, shapes) pytree, so a
        workload whose tick widths vary pays one extra trace per distinct
        composition during warmup."""
        if len(entries) == 1:
            staged, bn = entries[0]
            return [self.launch(staged, bn)]
        c0 = self._n_traces
        self.n_dispatches += 1
        batches = tuple(
            (jnp.asarray(s.x_pad), bn, s.adjs, jnp.asarray(s.pos_pad))
            for s, bn in entries)
        outs = self._jit_serve_many(batches)
        if self._n_traces > c0 and self.on_trace is not None:
            self.on_trace(self.adapter.trace_shape_many(
                [s for s, _ in entries]))
        return list(outs)

    def finish(self, out_dev: jax.Array, staged: "StagedBatch") -> np.ndarray:
        """COMPUTE-stage tail: block on the device result and crop it back
        to host answers (GNN: the seed rows)."""
        return self.adapter.finish(out_dev, staged)

    def run(self, x_sub: np.ndarray, mats: Dict[str, frdc.FRDCMatrix],
            seed_pos: np.ndarray, bn: tuple) -> np.ndarray:
        """Serial stage -> launch -> finish of one extracted subgraph.

        ``x_sub``: (n_sub, F) features of the subgraph nodes (global order);
        ``seed_pos``: positions of the seeds inside the subgraph. Returns
        (len(seed_pos), n_out) logits. The pipelined engine calls the three
        stages itself; composing them here keeps serial and pipelined
        serving bit-exact by construction.
        """
        staged = self.stage(x_sub, mats, seed_pos)
        return self.finish(self.launch(staged, bn), staged)

    def preset_water(self, n_max: int, g_max: Dict[str, int],
                     margin: float) -> None:
        """Set the water marks ``margin`` above probed maxima (pow2-rounded);
        a workload batch can only recompile by exceeding the margined bucket,
        and the monotone water then absorbs it after one compile."""
        n_pad = bucket_pow2(min(int(n_max * margin), self.node_cap),
                            self.NODE_BUCKET_FLOOR, self.node_cap)
        self._n_water = max(self._n_water, n_pad)
        for k, g in g_max.items():
            wkey = (self._n_water, k)
            g_pad = bucket_pow2(int(g * margin), self.GROUP_BUCKET_FLOOR)
            self._g_water[wkey] = max(self._g_water.get(wkey, 0), g_pad)


# ---------------------------------------------------------------------------
# Prepared batches — the extract-stage output of the serving pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StagedBatch:
    """One bucket-padded subgraph, ready for :meth:`ServeCore.launch`."""
    x_pad: np.ndarray               # (n_pad, F) zero-padded features
    adjs: Dict[str, dict]           # padded FRDC arrays per adjacency kind
    pos_pad: np.ndarray             # (max_batch,) seed positions, padded
    n_seeds: int


@dataclasses.dataclass
class PreparedGroup:
    """One serve core's share of a prepared batch: the staged subgraph of
    the uniq-seed subset ``sel`` (single-host sessions have one group; a
    sharded session one per owning shard)."""
    core: ServeCore
    sel: np.ndarray                 # positions inside the batch's uniq seeds
    staged: StagedBatch


@dataclasses.dataclass
class PreparedBatch:
    """Extract-stage output for one micro-batch of seeds: everything the
    compute stage needs, produced WITHOUT any device work — so building one
    can overlap the previous batch's in-flight jitted forward.

    ``inverse`` maps the uniq-seed rows back to request order;
    ``out_shape`` is the per-seed logits shape (used when ``groups`` is
    empty, i.e. zero seeds). ``bn`` is the frozen calibration CAPTURED AT
    EXTRACT TIME: a pipelined engine may see a feature update (and
    recalibration) land between staging batch i and launching it, and the
    launch must use the constants the features were staged under — never
    the session's live ``bn``."""
    n_uniq: int
    inverse: np.ndarray
    groups: List[PreparedGroup]
    out_shape: Tuple[int, ...] = ()
    bn: Optional[tuple] = None

    def launch(self) -> List[jax.Array]:
        """Dispatch every group's jitted forward (async under jax dispatch)
        with the CAPTURED calibration; returns the in-flight device results
        in group order. Deliberately takes no ``bn`` argument — passing the
        session's live stats here is exactly the staleness bug the capture
        prevents."""
        return [g.core.launch(g.staged, self.bn) for g in self.groups]

    def finish(self, devs: List[jax.Array]) -> np.ndarray:
        """Block on the device results and reassemble request-order logits."""
        out: Optional[np.ndarray] = None
        for g, dv in zip(self.groups, devs):
            logits = g.core.finish(dv, g.staged)
            if out is None:
                out = np.zeros((self.n_uniq,) + logits.shape[1:],
                               logits.dtype)
            out[g.sel] = logits
        if out is None:
            out = np.zeros((self.n_uniq,) + tuple(self.out_shape),
                           np.float32)
        return out[self.inverse]


def launch_prepared_many(prepared: List[PreparedBatch]
                         ) -> List[List[jax.Array]]:
    """Co-dispatch several prepared batches: every staged group is bucketed
    by its owning :class:`ServeCore` and each core issues ONE
    :meth:`ServeCore.launch_many` dispatch for its whole share — one device
    dispatch per core per tick instead of one per batch. Returns the
    per-batch device-handle lists in exactly the order
    ``[p.launch() for p in prepared]`` would, and each handle is bitwise
    identical to what the serial launches produce (the co-launched program
    is the serial bodies unrolled). Groups keep their batch's CAPTURED
    ``bn`` — co-launching never re-reads live calibration."""
    by_core: Dict[int, Tuple[ServeCore, list]] = {}
    slots: List[List[Optional[jax.Array]]] = []
    for bi, p in enumerate(prepared):
        slots.append([None] * len(p.groups))
        for gi, g in enumerate(p.groups):
            _, entries = by_core.setdefault(id(g.core), (g.core, []))
            entries.append((g.staged, p.bn, bi, gi))
    for core, entries in by_core.values():
        outs = core.launch_many([(s, bn) for s, bn, _, _ in entries])
        for (_, _, bi, gi), dv in zip(entries, outs):
            slots[bi][gi] = dv
    return slots


# ---------------------------------------------------------------------------
# Plan selection (default + tuner; paper §3.4)
# ---------------------------------------------------------------------------

def default_plan(family: str) -> SessionPlan:
    if family == "gcn":
        return SessionPlan(family, "bin",
                           layer_variants=GCN_SCHEME_VARIANTS["bin"])
    return SessionPlan(family, "fixed")


def tune_plan(data, family: str, qparams, repeats: int = 2) -> SessionPlan:
    """Time the legal end-to-end variant assignments on the actual graph
    (paper §3.4) and pick the fastest. ``data``: the host GraphData."""
    x = jnp.asarray(data.x)
    if family == "gcn":
        adj, adj_bin = data.adjacency("gcn"), data.adjacency("binary")
        cands = [
            tuner.Candidate(GCN_SCHEME_VARIANTS["full"], "s3_two_popc"),
            tuner.Candidate(GCN_SCHEME_VARIANTS["bin"], "s3_two_popc"),
            tuner.Candidate(GCN_SCHEME_VARIANTS["bin"], "s2_and_andnot"),
        ]

        def build(cand):
            scheme = ("bin" if cand.layer_variants[0][0] == "BMM.FBB"
                      else "full")
            def fwd(xx):
                return gnn.gcn_forward_bitgnn(
                    qparams, xx, adj, adj_bin, scheme=scheme,
                    trinary_mode=cand.trinary_mode)
            return fwd
    else:
        adj = data.adjacency("mean" if family == "sage" else "binary")
        fwd_fn = (gnn.sage_forward_bitgnn if family == "sage"
                  else gnn.saint_forward_bitgnn)
        cands = [tuner.Candidate(FIXED_VARIANTS, TRINARY_DEFAULT)]

        def build(cand):
            def fwd(xx):
                return fwd_fn(qparams, xx, adj)
            return fwd

    results = tuner.tune(build, (x,), cands, repeats=repeats)
    best = results[0]
    scheme = "fixed"
    if family == "gcn":
        scheme = ("bin" if best.candidate.layer_variants[0][0] == "BMM.FBB"
                  else "full")
    return SessionPlan(
        family=family, scheme=scheme,
        trinary_mode=best.candidate.trinary_mode,
        layer_variants=best.candidate.layer_variants,
        tuned_latency_s=best.latency_s,
        output_delta=best.output_delta)
