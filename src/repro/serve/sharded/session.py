"""ShardedGraphSession: one (graph, model) serving artifact split over P shards.

Compared with the single-host :class:`~repro.serve.gnn_session.
CompiledGraphSession`, the graph state is partitioned (contiguous
tile-row-aligned node ranges, :mod:`.planner`): each shard owns its feature
rows, its block of the CSR, an intra-shard FRDC adjacency and a bit-packed
halo adjacency over the boundary edges. Serving has two paths:

  * **routed subgraph** (the scale path): a k-hop query is answered by its
    seed's OWNING shard — the frontier is routed across shard boundaries
    (:mod:`.routing`), remote features and factorization-vector entries are
    fetched through the halo transport, and the owning shard's
    :class:`~repro.serve.session_core.ServeCore` runs the same bucketed
    jitted forward as the single-host session with the same frozen BN stats.
    Because the assembled subgraph, adjacency, features and calibration are
    identical, the outputs are bit-exact against single-host serving.

  * **distributed full pass**: layer-wise per-shard aggregation — each shard
    computes its output rows from ``intra @ local + halo @ remote``, where
    the remote operand arrives via halo exchange (:mod:`.halo`); for the
    binary-aggregation layer of the GCN "bin" scheme the exchanged rows are
    bit-PACKED (uint32 words, 32x smaller than fp) and the partial popc
    counts add exactly. This pass fills the per-shard full-logits caches and
    is the path whose halo bytes the benchmark reports. Its fp aggregations
    reassociate across the intra/halo split, so it matches single-host
    full-graph logits to fp tolerance (binary layers: exactly).

The pass itself is delegated to a :class:`~repro.serve.session_core.
LayerExecutor` running the family's layer program (``executor=``):

  * ``"host"`` — PR 2's host-orchestrated per-shard stages (the
    bit-exactness reference, runs on any device count);
  * ``"spmd"`` — each layer as ONE ``shard_map`` program over uniformly
    padded stacked shards with the halo exchange fused in
    (:mod:`.executor`); requires a mesh with a ``data`` axis of exactly P
    devices and matches the host executor bit-for-bit under shared BN
    constants.

BN calibration (``bn_mode=``): ``"single_host"`` runs one full-graph pass
through the shared :func:`~repro.serve.session_core.family_forward`
(bit-identical to the single-host session's calibration — the invariant
behind the exactness guarantee above); ``"distributed"`` computes each BN
site's (mu, sd) from the distributed pass itself (psum moments across
shards) so no host ever needs the whole graph — serving drift vs the anchor
is quantified in ``benchmarks/bench_sharded_serve.py``.

Artifacts (per-shard FRDC + CSR + routing table) serialize through the
checkpointer with a ``routing.json`` sidecar (now carrying the ``spmd``
uniform-dims/schedule field; older artifacts without it still load and
rebuild it); a restore re-builds the session without re-partitioning or
re-tuning.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import frdc
from repro.graphs import sampling
from repro.kernels import ops as kernel_ops
from repro.launch.mesh import make_shard_mesh
from repro.serve import adapters, session_core
from repro.serve.session_core import ServeCore, SessionPlan
from . import halo as halo_mod
from .executor import HostLayerExecutor, SpmdLayerExecutor
from .planner import ShardPart, ShardPlan, SpmdPlan
from .routing import RoutingTable, ShardedCSR
from .routing import khop_subgraph as routed_khop_subgraph

EXECUTORS = ("host", "spmd")
BN_MODES = ("single_host", "distributed")


class ShardedGraphSession:
    """Partitioned compiled serving artifact. See module docstring."""

    def __init__(self, graph, model, plan: SessionPlan, qparams,
                 shard_plan: ShardPlan, khop: int = 2, max_batch: int = 32,
                 use_pallas: bool = False, mesh=None,
                 executor: str = "host", bn_mode: str = "single_host"):
        if shard_plan.family != plan.family:
            raise ValueError(f"shard plan family {shard_plan.family!r} != "
                             f"session family {plan.family!r}")
        if executor not in EXECUTORS:
            raise ValueError(f"unknown executor {executor!r}; "
                             f"have {EXECUTORS}")
        if bn_mode not in BN_MODES:
            raise ValueError(f"unknown bn_mode {bn_mode!r}; have {BN_MODES}")
        kernel_ops.check_fused(plan.fused)
        self.graph = graph
        self.model = model
        self.plan = plan
        self.qparams = qparams
        self.shard_plan = shard_plan
        self.routing: RoutingTable = shard_plan.routing
        self.khop = khop
        self.max_batch = max_batch
        self.use_pallas = use_pallas
        self.mesh = mesh
        self.executor = executor
        self.bn_mode = bn_mode
        self.key = f"{graph.name}__{model.name}__P{shard_plan.n_shards}"
        self.feature_version = -1
        self.bn: Optional[tuple] = None
        self.halo_stats = halo_mod.HaloStats()
        self._caches: Optional[List[np.ndarray]] = None
        self._assembled: Optional[np.ndarray] = None
        self._invalidations = 0
        self._scsr: ShardedCSR = shard_plan.sharded_csr()
        self._adj_full: Optional[Dict[str, frdc.FRDCMatrix]] = None
        self._jit_calibrate = None
        self._executor_obj: Optional[session_core.LayerExecutor] = None
        self.program = session_core.build_layer_program(plan, qparams)
        # one bucketed serve core per shard (all composing ONE stateless
        # family adapter — the water marks live per core); a routed subgraph
        # can span the whole graph, so every core's node cap is the full
        # padded graph
        node_cap = -(-shard_plan.n_nodes // frdc.TILE) * frdc.TILE
        self.adapter = adapters.GNNAdapter(plan)
        self.cores = [ServeCore(plan, qparams, max_batch, node_cap,
                                use_pallas=use_pallas, adapter=self.adapter)
                      for _ in range(shard_plan.n_shards)]
        # observability callback cb(label, shape_dict), fanned out to every
        # per-shard core and (on build) the layer executor
        self._trace_hook = None

    # ------------------------------------------------------------ state ----
    @property
    def n_shards(self) -> int:
        return self.shard_plan.n_shards

    @property
    def parts(self) -> List[ShardPart]:
        return self.shard_plan.parts

    @property
    def compile_count(self) -> int:
        """Total jit traces across the per-shard bucketed forwards."""
        return sum(c.compile_count for c in self.cores)

    @property
    def compile_count_by_shard(self) -> List[int]:
        return [c.compile_count for c in self.cores]

    @property
    def dispatch_count(self) -> int:
        """Device dispatches across the per-shard serve cores (a
        multi-bucket co-launch counts 1 per participating core)."""
        return sum(c.n_dispatches for c in self.cores)

    @property
    def invalidations(self) -> int:
        return self._invalidations

    def _x_blocks(self) -> List[np.ndarray]:
        x = self.graph.data.x
        return [x[p.row_start:p.row_end] for p in self.parts]

    def _dinv_blocks(self) -> Optional[List[np.ndarray]]:
        if self.parts[0].dinv is None:
            return None
        return [p.dinv for p in self.parts]

    def _use_mesh(self) -> bool:
        return (self.mesh is not None
                and self.mesh.shape.get("data", 0) == self.n_shards)

    def set_mesh(self, mesh) -> None:
        """Swap the halo transport (None = host loopback). Numerics are
        transport-independent; only the exchange mechanism changes."""
        if mesh is not self.mesh:
            self.mesh = mesh
            self._executor_obj = None

    # ------------------------------------------------------- executor ------
    @property
    def layer_executor(self) -> session_core.LayerExecutor:
        """The distributed-pass executor (built lazily; rebuilt on
        ``set_mesh``). ``executor="spmd"`` auto-builds a shard mesh when
        none was attached and raises when the host cannot supply one."""
        if self._executor_obj is None:
            if self.executor == "spmd":
                mesh = self.mesh if self._use_mesh() else \
                    make_shard_mesh(self.n_shards)
                if mesh is None:
                    raise RuntimeError(
                        f"executor='spmd' needs {self.n_shards} devices "
                        f"(have {len(jax.devices())}); force them with "
                        f"XLA_FLAGS=--xla_force_host_platform_device_count")
                self.mesh = mesh
                self._executor_obj = SpmdLayerExecutor(
                    self.parts, self.shard_plan.spmd_plan(), self.plan,
                    self.halo_stats, mesh, use_pallas=self.use_pallas)
            else:
                self._executor_obj = HostLayerExecutor(
                    self.parts, self.shard_plan.spmd_plan(), self.plan,
                    self.halo_stats, self.routing,
                    mesh=self.mesh if self._use_mesh() else None,
                    use_pallas=self.use_pallas)
            self._wire_executor_hook()
        return self._executor_obj

    def set_trace_hook(self, cb) -> None:
        """Wire an observability callback ``cb(label, shape_dict)`` to fire
        on every NEW jit trace of any per-shard serve core or layer-executor
        program (the engines' recompile watchdog). ``None`` unwires. A lazy
        executor built later inherits the hook."""
        self._trace_hook = cb
        for i, core in enumerate(self.cores):
            if cb is None:
                core.on_trace = None
            else:
                core.on_trace = (lambda shape, _i=i:
                                 cb(f"shard{_i}/core", shape))
        self._wire_executor_hook()

    def _wire_executor_hook(self) -> None:
        if self._executor_obj is None:
            return
        cb = self._trace_hook
        self._executor_obj.on_trace = (
            None if cb is None
            else (lambda label, shape: cb(f"executor/{label}", shape)))

    @property
    def executor_compile_count(self) -> int:
        """Jit traces of the distributed-pass layer programs — exactly one
        per (layer program, mode, shapes) in steady state for both
        executors (the host executor traces a stage + an operand program
        per exchange layer; SPMD one shard_map program per layer)."""
        return (0 if self._executor_obj is None
                else self._executor_obj.compile_count)

    # ------------------------------------------------------- calibrate -----
    def _calibrate_fn(self):
        """The shared full-graph calibration forward — the SAME jitted
        computation the single-host session freezes its BN stats from, so a
        sharded and a single-host session over one graph agree bit-for-bit
        on the calibration constants."""
        if self._jit_calibrate is None:
            d = self.graph.data
            fam = self.plan.family
            if fam == "gcn":
                adjs = {"adj": d.adjacency("gcn"),
                        "bin": d.adjacency("binary")}
            elif fam == "sage":
                adjs = {"mean": d.adjacency("mean")}
            else:
                adjs = {"sum": d.adjacency("binary")}
            self._adj_full = adjs
            plan, qparams, use_pallas = self.plan, self.qparams, \
                self.use_pallas

            def full(x):
                return session_core.family_forward(
                    plan, qparams, x, adjs, use_pallas=use_pallas,
                    return_bn_stats=True)

            self._jit_calibrate = jax.jit(full)
        return self._jit_calibrate

    def sync(self) -> None:
        """Adopt the store's current features: recalibrate BN and refresh
        the per-shard logits caches through the DISTRIBUTED layer-wise pass
        (the configured executor). ``bn_mode="single_host"`` freezes the
        stats from the shared full-graph anchor forward first;
        ``"distributed"`` computes them inside the pass itself (psum
        moments), so one run both calibrates and fills the caches and no
        host ever materializes the full graph. No-op when current."""
        if self.feature_version == self.graph.version:
            return
        invalidated = self.feature_version >= 0
        if self.bn_mode == "distributed":
            self._caches, bn = self.layer_executor.run_pass(
                self.program, self._x_blocks(), None, calibrate=True)
            self.bn = bn
        else:
            _, bn = self._calibrate_fn()(jnp.asarray(self.graph.data.x))
            self.bn = bn
            self._caches, _ = self.layer_executor.run_pass(
                self.program, self._x_blocks(), self.bn)
        self._assembled = None
        self.feature_version = self.graph.version
        if invalidated:
            self._invalidations += 1

    # ----------------------------------------------------- full pass -------
    def run_distributed_pass(self) -> List[np.ndarray]:
        """One distributed full pass with the CURRENT frozen calibration
        (no cache mutation) — the benchmark's executor latency probe."""
        self.sync()
        blocks, _ = self.layer_executor.run_pass(
            self.program, self._x_blocks(), self.bn)
        return blocks

    # ------------------------------------------------------ full path ------
    def full_logits(self) -> np.ndarray:
        """Full-graph logits assembled from the per-shard caches (each
        filled by the distributed pass). The concatenation is memoized per
        feature version — the full-cache serve path gathers from it every
        tick."""
        self.sync()
        if self._assembled is None:
            self._assembled = np.concatenate(self._caches, axis=0)
        return self._assembled

    # -------------------------------------------------- subgraph path ------
    def _extract(self, uniq_seeds: np.ndarray):
        """Routed k-hop extraction + subgraph FRDC build for one owner's
        seed group (host-side; also used by warmup shape probing). Same
        prepared-subgraph object as the single-host extractor — the routed
        expansion is bit-identical to ``sampling.khop_subgraph``."""
        ex = sampling.ExtractedSubgraph(*routed_khop_subgraph(
            self._scsr, uniq_seeds, self.khop))
        dinv_blocks = self._dinv_blocks()
        dinv_sub = None
        if dinv_blocks is not None:
            dinv_sub = halo_mod.gather_rows(dinv_blocks, self.routing,
                                            ex.sub_nodes)
        mats = self.adapter.sub_operands(ex.sub_nodes.size, ex.sub_edges,
                                         dinv_sub)
        return ex.sub_nodes, mats, ex.seed_pos

    def prepare_batch(self, seeds: np.ndarray) -> session_core.PreparedBatch:
        """EXTRACT stage: routed k-hop extraction, halo feature fetch and
        bucket padding for every owner group in the batch — pure host work
        (the ``serve/x`` halo bytes are accounted here, where the gather
        happens). The engine's single-owner queues make this one group per
        batch in practice; mixed-owner batches stage one group per owner."""
        self.sync()
        seeds = np.asarray(seeds, np.int64)
        uniq, inverse = np.unique(seeds, return_inverse=True)
        owners = self.routing.owner(uniq)
        groups = []
        for s in np.unique(owners):
            sel = np.nonzero(owners == s)[0]
            sub_nodes, mats, seed_pos = self._extract(uniq[sel])
            x_sub = halo_mod.gather_rows(self._x_blocks(), self.routing,
                                         sub_nodes, home=int(s),
                                         stats=self.halo_stats, tag="serve/x")
            staged = self.cores[int(s)].stage(x_sub, mats, seed_pos)
            groups.append(session_core.PreparedGroup(
                core=self.cores[int(s)], sel=sel, staged=staged))
        return session_core.PreparedBatch(n_uniq=uniq.size, inverse=inverse,
                                          groups=groups,
                                          out_shape=self._out_shape(),
                                          bn=self.bn)

    def launch_batch(self, prepared) -> list:
        """COMPUTE-stage head: dispatch every owner group's jitted forward
        (with the calibration captured when the batch was staged)."""
        return prepared.launch()

    def finish_batch(self, prepared, devs) -> np.ndarray:
        """COMPUTE-stage tail: block and merge owner groups back into
        request order."""
        return prepared.finish(devs)

    def serve_subgraph(self, seeds: np.ndarray) -> np.ndarray:
        """Micro-batched node-level inference across shards: group the batch
        by owning shard (routing table), answer each group on its owner, and
        merge the logits back into request order. Serial composition of the
        same prepare/launch/finish stages the pipelined engine drives."""
        prepared = self.prepare_batch(seeds)
        return self.finish_batch(prepared, self.launch_batch(prepared))

    def seed_halo_tiles(self, node: int) -> frozenset:
        """Cheap per-seed halo signature for halo-aware batch formation: the
        FRDC tile ids (global node id // TILE) of the seed's REMOTE 1-hop
        neighbors — a one-CSR-row proxy for which halo tiles the seed's
        k-hop closure will request over the ``serve/x`` gather. Seeds with
        overlapping signatures share halo traffic when co-batched."""
        owner = int(self.routing.owner(np.asarray([node]))[0])
        lo, hi = self.routing.shard_range(owner)
        nbrs = self._scsr.shards[owner].neighbors(int(node) - lo)
        remote = nbrs[(nbrs < lo) | (nbrs >= hi)]
        return frozenset((remote // frdc.TILE).tolist())

    def _out_shape(self) -> tuple:
        if self._caches is not None:
            return self._caches[0].shape[1:]
        q = self.qparams
        last = q[-2] if self.plan.family == "sage" else q[-1]
        # BinTensor of W.T: packed rows = out features
        return (last.packed.shape[0],)

    def warmup(self, rng: Optional[np.random.Generator] = None,
               probes: int = 16, margin: float = 1.125) -> int:
        """Per-shard high-water warmup: probe ``probes`` max-width batches
        host-side, route each probe's seeds to their owners to find every
        shard's steady node/group maxima, preset the water marks, then run
        one real forward per shard. Returns compiles triggered."""
        rng = rng or np.random.default_rng(0)
        before = self.compile_count
        self.sync()
        n = self.shard_plan.n_nodes
        n_max = [0] * self.n_shards
        g_max: List[Dict[str, int]] = [{} for _ in range(self.n_shards)]

        def _probe(s: int, seeds: np.ndarray) -> None:
            sub_nodes, mats, _ = self._extract(seeds)
            n_max[s] = max(n_max[s], sub_nodes.size)
            for k, m in mats.items():
                g_max[s][k] = max(g_max[s].get(k, 0), m.n_groups)

        for _ in range(probes):
            seeds = np.unique(rng.integers(0, n, size=self.max_batch))
            owners = self.routing.owner(seeds)
            for s in np.unique(owners):
                _probe(s, seeds[owners == s])
            # steady state forms SINGLE-owner batches up to max_batch wide
            # (per-owner queues), so a mixed-owner probe understates every
            # shard's closure — also probe each shard at full batch width
            # from its own contiguous node range
            for s in range(self.n_shards):
                lo, hi = self.routing.shard_range(s)
                if hi > lo:
                    _probe(s, np.unique(rng.integers(lo, hi,
                                                     size=self.max_batch)))
        for s, core in enumerate(self.cores):
            if n_max[s] == 0:
                continue
            core.preset_water(n_max[s], g_max[s], margin)
        self.serve_subgraph(rng.integers(0, n, size=self.max_batch))
        return self.compile_count - before

    # ------------------------------------------------------- artifact ------
    def fingerprint(self) -> dict:
        return session_core.session_fingerprint(self.graph, self.model)

    def _state(self) -> dict:
        shards = []
        for p in self.parts:
            shards.append({
                "intra": {k: session_core.frdc_arrays(m)
                          for k, m in p.intra.items()},
                "halo": {k: session_core.frdc_arrays(m)
                         for k, m in p.halo.items()},
                "halo_nodes": p.halo_nodes,
                "indptr": p.indptr, "indices": p.indices,
                **({} if p.dinv is None else {"dinv": p.dinv}),
            })
        return {"qparams": self.qparams, "shards": shards}

    def save(self, directory: Path) -> None:
        """Serialize per-shard FRDC + CSR + routing table via the
        checkpointer; plan/fingerprint/dims in the ``routing.json`` sidecar
        (format documented in the README next to ``plan.json``)."""
        self.sync()
        directory = Path(directory)
        ckpt = Checkpointer(directory, keep=1)
        ckpt.save(0, self._state(), blocking=True)
        sidecar = dict(
            plan=self.plan.to_json(), fingerprint=self.fingerprint(),
            khop=self.khop, max_batch=self.max_batch,
            n_shards=self.n_shards,
            routing=self.routing.to_json(),
            spmd=self.shard_plan.spmd_plan().to_json(),
            shards=[dict(
                row_start=p.row_start, row_end=p.row_end, n_halo=p.n_halo,
                intra_dims={k: [m.n_rows, m.n_cols, m.nnz]
                            for k, m in p.intra.items()},
                halo_dims={k: [m.n_rows, m.n_cols, m.nnz]
                           for k, m in p.halo.items()},
            ) for p in self.parts])
        (directory / "routing.json").write_text(json.dumps(sidecar))

    @classmethod
    def load(cls, directory: Path, graph, model, khop: Optional[int] = None,
             max_batch: Optional[int] = None, use_pallas: bool = False,
             mesh=None, executor: str = "host",
             bn_mode: str = "single_host", bspmm_block="unchanged",
             fused="unchanged",
             ) -> Optional["ShardedGraphSession"]:
        """Restore a sharded artifact WITHOUT re-partitioning or re-tuning;
        returns None on any mismatch so the caller replans. ``executor`` /
        ``bn_mode`` are runtime choices, not artifact properties — any
        artifact serves under either executor; pre-``spmd``-field sidecars
        rebuild the uniform-dims plan from the restored parts."""
        directory = Path(directory)
        sidecar_path = directory / "routing.json"
        sidecar = session_core.load_sidecar(
            sidecar_path, required=("plan", "fingerprint", "khop",
                                    "max_batch", "n_shards", "routing",
                                    "shards"))
        if sidecar is None:
            return None
        if khop is not None and sidecar["khop"] != khop:
            return None
        if max_batch is not None and sidecar["max_batch"] != max_batch:
            return None
        try:
            plan = SessionPlan.from_json(sidecar["plan"])
        except (KeyError, TypeError, ValueError) as e:
            raise session_core.ArtifactError(sidecar_path, field="plan",
                                             detail=repr(e))
        if session_core.session_fingerprint(graph, model) \
                != sidecar["fingerprint"]:
            return None
        # trace-time kernel choices: a different block shape or fused
        # selection must recompile
        if bspmm_block != "unchanged" and plan.bspmm_block != bspmm_block:
            return None
        if fused != "unchanged" and plan.fused != fused:
            return None
        fam = model.family
        has_dinv = fam in ("gcn", "sage")
        kinds = session_core.FAMILY_ADJ_KINDS[fam]
        scale_extra = session_core.ADJ_SCALE_FIELDS[fam]

        def frdc_like(kind):
            # halo matrices carry the same scale fields as intra ones
            return {f: np.zeros(0)
                    for f in session_core.FRDC_BASE_FIELDS
                    + scale_extra[kind]}

        like_shards = []
        for sd in sidecar["shards"]:
            like_shards.append({
                "intra": {k: frdc_like(k) for k in kinds},
                "halo": {k: frdc_like(k) for k in kinds},
                "halo_nodes": np.zeros(0, np.int64),
                "indptr": np.zeros(0, np.int64),
                "indices": np.zeros(0, np.int64),
                **({"dinv": np.zeros(0)} if has_dinv else {}),
            })
        like = {"qparams": session_core.quantize_family(fam, model.params),
                "shards": like_shards}
        # typed restore: missing/mismatched checkpoint -> None (recompile),
        # truncated/corrupt npz or manifest -> ArtifactError naming the file
        state = session_core.restore_artifact_state(directory, like)
        if state is None:
            return None

        try:
            routing = RoutingTable.from_json(sidecar["routing"])
        except (KeyError, TypeError, ValueError) as e:
            raise session_core.ArtifactError(sidecar_path, field="routing",
                                             detail=repr(e))
        parts = []
        for s, (sd, st) in enumerate(zip(sidecar["shards"],
                                         state["shards"])):
            intra = {k: session_core.frdc_rebuild(st["intra"][k],
                                                  *sd["intra_dims"][k])
                     for k in kinds}
            halo_m = {k: session_core.frdc_rebuild(st["halo"][k],
                                                   *sd["halo_dims"][k])
                      for k in kinds}
            parts.append(ShardPart(
                index=s, row_start=int(sd["row_start"]),
                row_end=int(sd["row_end"]),
                halo_nodes=np.asarray(st["halo_nodes"], np.int64),
                intra=intra, halo=halo_m,
                indptr=np.asarray(st["indptr"], np.int64),
                indices=np.asarray(st["indices"], np.int64),
                dinv=(np.asarray(st["dinv"]) if has_dinv else None)))
        spmd = (SpmdPlan.from_json(sidecar["spmd"])
                if "spmd" in sidecar else None)
        shard_plan = ShardPlan(family=fam, routing=routing, parts=parts,
                               n_nodes=int(graph.data.n_nodes),
                               n_edges=int(graph.data.n_edges), spmd=spmd)
        return cls(graph, model, plan,
                   session_core.coerce_quant(state["qparams"]), shard_plan,
                   khop=sidecar["khop"], max_batch=sidecar["max_batch"],
                   use_pallas=use_pallas, mesh=mesh, executor=executor,
                   bn_mode=bn_mode)
