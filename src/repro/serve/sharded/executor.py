"""Layer executors of the distributed full pass.

Two implementations of :class:`repro.serve.session_core.LayerExecutor`, both
running the SAME family layer program (:func:`~repro.serve.session_core.
build_layer_program`) over the SAME uniformly padded per-shard operands
(:class:`~.planner.SpmdPlan`), through the SAME traced step body
(:func:`layer_compute`):

  * :class:`HostLayerExecutor` — host-orchestrated, the reference that runs
    on any device count: each layer executes as P sequential jitted
    per-shard stage programs with the halo exchange as a host-side step
    between them (mesh ring collective when a mesh is attached, loopback
    otherwise). Compute serializes against communication — the
    orchestration overhead the SPMD path removes.

  * :class:`SpmdLayerExecutor` — each layer is ONE ``shard_map`` program
    over the shard-stacked operands: BN -> dense transform -> fused
    ``ppermute`` ring exchange -> intra+halo aggregation -> combine, all
    inside a single jitted SPMD computation, so a real multi-host
    deployment overlaps compute with the exchange.

Sharing ``layer_compute`` (and the padded shapes) between the two is what
makes them BIT-IDENTICAL: XLA applies fusion-dependent fp rewrites — FMA
contraction of ``a + b*c``, factoring of ``a*r + b*r`` — so the same math
split into different jit programs rounds differently. Both executors
therefore jit the exact same step body, differing only in where the halo
operand comes from (a parameter vs the in-program ring exchange), and the
shared aggregation applies the row scale once after the intra+halo add
(:func:`repro.kernels.ops.serve_fp_pair`) so the factored form is already
explicit. Per-row ops are exact under row padding and padded FRDC
groups/rows/columns carry no bits, so padding does not perturb real rows.

Distributed BN calibration (``calibrate=True``): each BN site's (mu, sd)
comes from the pass itself — masked per-shard moment partials combined with
``psum`` across the mesh (SPMD) or host-side summation (host executor, same
formula) — so calibration no longer needs the single-host full-graph
anchor.

Halo byte accounting is recorded OUTSIDE any trace — the SPMD executor adds
the static schedule's ``MeshHaloPlan.payload_bytes`` per jitted step
invocation, so steady-state passes that never retrace still account
correctly (and trace-time side effects never double-count).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import frdc
from repro.kernels import ops as kernel_ops
from repro.serve import session_core
from repro.serve.session_core import LayerExecutor, LayerStep, SessionPlan
from . import halo as halo_mod
from .planner import ShardPart, SpmdPlan
from .routing import RoutingTable


def layer_compute(step: LayerStep, trinary_mode: str, use_pallas: bool,
                  st, bn_stats, rem, intra, halo, fused: bool = False):
    """The traced core of one layer step, shared verbatim by both executors
    (identical jaxpr => identical XLA rewrites => bit-identical results).

    ``st``: this shard's padded carried state; ``bn_stats``: (mu, sd) or
    None; ``rem``: the (n_halo_pad, F) exchanged halo operand (None for
    exchange-free steps); ``intra``/``halo``: the shard's uniformly padded
    FRDC matrices of ``step.kind``.

    ``fused=True`` (``SessionPlan.fused``, only where the kernels are
    active) emits the whole step — BN, transform, intra+halo aggregation,
    combine — as ONE Pallas launch through
    :func:`repro.kernels.fused_layer.fused_call` instead of separate
    dispatches; the exchange stays outside (``rem`` is already this
    shard's gathered halo operand)."""
    if fused and kernel_ops.kernels_active(use_pallas):
        return _fused_layer_compute(step, trinary_mode, st, bn_stats, rem,
                                    intra, halo)
    z = session_core.apply_bn(st, *bn_stats) if bn_stats is not None else st
    operand, aux = step.pre(z)
    if step.kind is None:
        y = operand
    elif step.packed:
        y = kernel_ops.serve_counts(intra, operand, trinary_mode,
                                    use_pallas) \
            + kernel_ops.serve_counts(halo, rem, trinary_mode, use_pallas)
    else:
        y = kernel_ops.serve_fp_pair(intra, halo, operand, rem, use_pallas)
    return step.post(aux, y)


def _fused_layer_compute(step: LayerStep, trinary_mode: str,
                         st, bn_stats, rem, intra, halo):
    """One-launch form of :func:`layer_compute`: the step body traced inside
    a single ``fused_call`` kernel, aggregating through the value-level
    walks (kernel-order, so bitwise identical to the unfused kernels under
    the same jit). FRDC operands cross the kernel boundary as their array
    fields — the static row/col counts must stay python ints."""
    from repro.kernels import fused_layer

    dims = None
    ia = ha = None
    if step.kind is not None:
        ia = session_core.frdc_arrays(intra)
        ha = session_core.frdc_arrays(halo)
        dims = (intra.n_rows, intra.n_cols, halo.n_rows, halo.n_cols)

    def body(st_, bn_, rem_, ia_, ha_):
        z = session_core.apply_bn(st_, *bn_) if bn_ is not None else st_
        operand, aux = step.pre(z)
        if step.kind is None:
            y = operand
        else:
            im = session_core.frdc_rebuild(ia_, dims[0], dims[1])
            hm = session_core.frdc_rebuild(ha_, dims[2], dims[3])
            if step.packed:
                y = fused_layer.agg_counts(im, operand, trinary_mode) \
                    + fused_layer.agg_counts(hm, rem_, trinary_mode)
            else:
                y = fused_layer.agg_fp_pair(im, hm, operand, rem_)
        return step.post(aux, y)

    return fused_layer.fused_call(body, st, bn_stats, rem, ia, ha)


class _PaddedExecutor(LayerExecutor):
    """Shared state of both executors: the uniformly padded per-shard FRDC
    operands and the trace counter."""

    def __init__(self, parts: List[ShardPart], spmd: SpmdPlan,
                 plan: SessionPlan, stats: halo_mod.HaloStats,
                 use_pallas: bool = False):
        self.parts = parts
        self.spmd = spmd
        self.plan = plan
        self.stats = stats
        self.use_pallas = use_pallas
        self._n_traces = 0
        # observability hook: called as on_trace(label, shape_dict) from
        # inside a jitted program body — a python side effect that runs once
        # per NEW trace, exactly like the _n_traces counter above it
        self.on_trace = None
        self._fns: Dict[tuple, callable] = {}
        npd, nhp = spmd.n_local_pad, spmd.n_halo_pad
        # per-kind uniformly padded per-shard matrices + fixed field order
        self._fields: Dict[str, Tuple[tuple, tuple]] = {}
        self._intra: Dict[str, List[frdc.FRDCMatrix]] = {}
        self._halo: Dict[str, List[frdc.FRDCMatrix]] = {}
        for kind in parts[0].intra:
            self._intra[kind] = frdc.pad_frdc_uniform(
                [pt.intra[kind] for pt in parts], npd, npd,
                spmd.intra_groups[kind])
            self._halo[kind] = frdc.pad_frdc_uniform(
                [pt.halo[kind] for pt in parts], npd, nhp,
                spmd.halo_groups[kind])
            arrs_i = session_core.frdc_arrays(self._intra[kind][0])
            arrs_h = session_core.frdc_arrays(self._halo[kind][0])
            self._fields[kind] = (tuple(sorted(arrs_i)),
                                  tuple(sorted(arrs_h)))

    @property
    def compile_count(self) -> int:
        """Jit traces of the layer stage programs — exactly one per
        (program step, mode, shapes) in steady state."""
        return self._n_traces

    def _pad_state(self, xs: List[np.ndarray]) -> List[np.ndarray]:
        npd = self.spmd.n_local_pad
        out = []
        for b in xs:
            b = np.asarray(b)
            buf = np.zeros((npd,) + b.shape[1:], b.dtype)
            buf[:b.shape[0]] = b
            out.append(buf)
        return out

    def _mat_args(self, kind: str, s: int) -> List[jax.Array]:
        ifields, hfields = self._fields[kind]
        ia = session_core.frdc_arrays(self._intra[kind][s])
        ha = session_core.frdc_arrays(self._halo[kind][s])
        return [ia[f] for f in ifields] + [ha[f] for f in hfields]


class HostLayerExecutor(_PaddedExecutor):
    """Host-orchestrated distributed pass (sequential per-shard stages)."""

    name = "host"

    def __init__(self, parts: List[ShardPart], spmd: SpmdPlan,
                 plan: SessionPlan, stats: halo_mod.HaloStats,
                 routing: RoutingTable, mesh=None,
                 use_pallas: bool = False):
        super().__init__(parts, spmd, plan, stats, use_pallas=use_pallas)
        self.routing = routing
        self.mesh = mesh
        # cached per-shard mat args (device arrays, built once)
        self._margs = {kind: [self._mat_args(kind, s)
                              for s in range(len(parts))]
                       for kind in parts[0].intra}

    # ----------------------------------------------------------- exchange --
    def _exchange(self, blocks: List[np.ndarray], tag: str
                  ) -> List[np.ndarray]:
        """Fetch every shard's halo rows of a per-shard row-block operand —
        device collectives over the mesh when one is attached, host loopback
        otherwise. Returns per-shard (n_halo_pad, F) operands (zero-padded
        so padded halo columns aggregate exact zeros)."""
        blocks = [np.asarray(b) for b in blocks]
        if self.mesh is not None:
            # the SpmdPlan's schedule is the same send/recv table (only the
            # receive buffer is wider — mesh_exchange slices it back down),
            # so no second MeshHaloPlan is ever built.
            gathered = halo_mod.mesh_exchange(
                self.mesh, blocks, self.spmd.mesh_plan,
                stats=self.stats, tag=tag)
        else:
            gathered = [
                halo_mod.gather_rows(blocks, self.routing, p.halo_nodes,
                                     home=p.index, stats=self.stats,
                                     tag=tag)
                for p in self.parts]
        nhp = self.spmd.n_halo_pad
        out = []
        for p, g in zip(self.parts, gathered):
            buf = np.zeros((nhp,) + blocks[0].shape[1:], blocks[0].dtype)
            buf[:p.n_halo] = g
            out.append(buf)
        return out

    # ------------------------------------------------------ stage programs --
    def _stage_fn(self, program: Tuple[LayerStep, ...], i: int,
                  with_bn: bool):
        """The jitted per-shard stage of step ``i`` — the SAME
        :func:`layer_compute` body the SPMD program traces, with the halo
        operand as a parameter instead of an in-program collective. One
        executable serves every shard (uniform padded shapes; the FRDC
        arrays are traced arguments)."""
        key = ("stage", i, with_bn)
        if key in self._fns:
            return self._fns[key]
        step = program[i]
        trinary, up = self.plan.trinary_mode, self.use_pallas
        fused = self.plan.fused
        npd, nhp = self.spmd.n_local_pad, self.spmd.n_halo_pad
        ifields, hfields = self._fields[step.kind] if step.kind else ((), ())

        def fn(st, *rest):
            self._n_traces += 1
            if self.on_trace is not None:
                self.on_trace(f"{self.name}/stage{i}",
                              dict(n_local_pad=npd, n_halo_pad=nhp,
                                   with_bn=with_bn))
            it = iter(rest)
            bn_stats = (next(it), next(it)) if with_bn else None
            rem = intra = halo = None
            if step.kind is not None:
                rem = next(it)
                intra = session_core.frdc_rebuild(
                    {f: next(it) for f in ifields}, npd, npd)
                halo = session_core.frdc_rebuild(
                    {f: next(it) for f in hfields}, npd, nhp)
            return layer_compute(step, trinary, up, st, bn_stats, rem,
                                 intra, halo, fused=fused)

        self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _operand_fn(self, program: Tuple[LayerStep, ...], i: int,
                    with_bn: bool):
        """Jitted BN+pre producing the exchange operand (the pre chain is
        fusion-stable, so recomputing it inside the stage program rounds
        identically)."""
        key = ("operand", i, with_bn)
        if key in self._fns:
            return self._fns[key]
        step = program[i]

        def fn(st, *bn_stats):
            self._n_traces += 1
            if self.on_trace is not None:
                self.on_trace(f"{self.name}/operand{i}",
                              dict(with_bn=with_bn))
            z = session_core.apply_bn(st, *bn_stats) if with_bn else st
            return step.pre(z)[0]

        self._fns[key] = jax.jit(fn)
        return self._fns[key]

    # ---------------------------------------------------------------- pass --
    def run_pass(self, program: Tuple[LayerStep, ...], xs: List[np.ndarray],
                 bn: Optional[tuple], calibrate: bool = False):
        state = [jnp.asarray(b) for b in self._pad_state(xs)]
        collected = []
        for i, step in enumerate(program):
            with_bn = step.bn_site is not None
            if with_bn:
                if calibrate:
                    site = session_core.distributed_moments(
                        [s[:p.n_local]
                         for s, p in zip(state, self.parts)])
                    collected.append(site)
                else:
                    site = bn[step.bn_site]
                bn_args = [jnp.asarray(site[0]), jnp.asarray(site[1])]
            else:
                bn_args = []
            stage = self._stage_fn(program, i, with_bn)
            if step.kind is not None:
                pre = self._operand_fn(program, i, with_bn)
                operands = [np.asarray(pre(s, *bn_args))[:p.n_local]
                            for s, p in zip(state, self.parts)]
                halo_in = self._exchange(operands, step.tag)
                state = [stage(s, *bn_args, jnp.asarray(rem),
                               *self._margs[step.kind][p.index])
                         for s, rem, p in zip(state, halo_in, self.parts)]
            else:
                state = [stage(s, *bn_args) for s in state]
        blocks = [np.asarray(s)[:p.n_local]
                  for s, p in zip(state, self.parts)]
        return blocks, (tuple(collected) if calibrate else None)


class SpmdLayerExecutor(_PaddedExecutor):
    """One ``shard_map`` program per layer over the stacked padded shards."""

    name = "spmd"

    def __init__(self, parts: List[ShardPart], spmd: SpmdPlan,
                 plan: SessionPlan, stats: halo_mod.HaloStats, mesh,
                 use_pallas: bool = False):
        p = spmd.n_shards
        if mesh is None or "data" not in mesh.axis_names \
                or mesh.shape["data"] != p or mesh.devices.size != p:
            raise ValueError(
                f"SPMD executor needs a mesh with a 'data' axis of exactly "
                f"{p} devices (make_shard_mesh({p})); got {mesh}")
        super().__init__(parts, spmd, plan, stats, use_pallas=use_pallas)
        self.mesh = mesh
        # shard-stacked operand arrays: dict-field order of _fields[kind]
        self._stacked: Dict[str, List[jax.Array]] = {}
        for kind in parts[0].intra:
            istk = frdc.stack_frdc(self._intra[kind])
            hstk = frdc.stack_frdc(self._halo[kind])
            ifields, hfields = self._fields[kind]
            self._stacked[kind] = [istk[f] for f in ifields] \
                + [hstk[f] for f in hfields]
        # the SPMD path only ever reads the stacked copies — drop the
        # per-shard padded matrices so the operands aren't held twice.
        self._intra.clear()
        self._halo.clear()
        mp = spmd.mesh_plan
        self._sched = [jnp.asarray(a) for pair
                       in zip(mp.send_idx, mp.recv_pos) for a in pair]
        self._perms = halo_mod.ring_perms(p)
        self._n_local = jnp.asarray(
            np.array([[pt.n_local] for pt in parts], np.int32))

    # ------------------------------------------------------- step programs --
    def _step_fn(self, program: Tuple[LayerStep, ...], i: int,
                 calibrate: bool):
        key = (i, bool(calibrate))
        if key in self._fns:
            return self._fns[key]
        from jax.sharding import PartitionSpec as PS
        step = program[i]
        p = self.spmd.n_shards
        npd, nhp = self.spmd.n_local_pad, self.spmd.n_halo_pad
        kind, nshift = step.kind, p - 1
        trinary, up = self.plan.trinary_mode, self.use_pallas
        fused = self.plan.fused
        perms = self._perms
        ifields, hfields = self._fields[kind] if kind else ((), ())
        frozen_bn = step.bn_site is not None and not calibrate
        calib_bn = step.bn_site is not None and calibrate

        def body(*args):
            self._n_traces += 1            # python side effect: trace count
            if self.on_trace is not None:
                self.on_trace(f"{self.name}/step{i}",
                              dict(n_local_pad=npd, n_halo_pad=nhp,
                                   calibrate=calibrate))
            it = iter(args)
            st = next(it)[0]               # carried state (n_local_pad, F)
            nloc = next(it)[0][0]          # this shard's real row count
            bn_stats = None
            if frozen_bn:
                bn_stats = (next(it), next(it))
            elif calib_bn:
                # distributed BN moments: padded rows carry garbage from
                # earlier per-row stages, so they are masked out of the
                # partial sums; psum combines the per-shard partials.
                rows = jnp.arange(st.shape[0], dtype=jnp.int32)
                mask = (rows < nloc)[:, None].astype(st.dtype)
                cnt = jax.lax.psum(nloc.astype(jnp.float32), "data")
                s1 = jax.lax.psum(
                    jnp.sum(st * mask, axis=0, keepdims=True), "data")
                s2 = jax.lax.psum(
                    jnp.sum(st * st * mask, axis=0, keepdims=True), "data")
                bn_stats = session_core.moments_from_sums(s1, s2, cnt)
            rem = intra = halo = None
            if kind is not None:
                intra = session_core.frdc_rebuild(
                    {f: next(it)[0] for f in ifields}, npd, npd)
                halo = session_core.frdc_rebuild(
                    {f: next(it)[0] for f in hfields}, npd, nhp)
                sched = [next(it)[0] for _ in range(2 * nshift)]
                # the exchange operand is the same BN+pre chain
                # layer_compute recomputes below — fusion-stable, so the
                # two computations round identically.
                z = (session_core.apply_bn(st, *bn_stats)
                     if bn_stats is not None else st)
                operand, _ = step.pre(z)
                rem = halo_mod.ring_scatter(operand, sched[0::2],
                                            sched[1::2], perms, nhp)
            new = layer_compute(step, trinary, up, st, bn_stats, rem,
                                intra, halo, fused=fused)
            if calib_bn:
                return new[None], bn_stats[0][None], bn_stats[1][None]
            return new[None]

        in_specs = [PS("data"), PS("data")]
        if frozen_bn:
            in_specs += [PS(), PS()]
        if kind is not None:
            in_specs += [PS("data")] * (len(ifields) + len(hfields)
                                        + 2 * nshift)
        out_specs = (PS("data"),) * 3 if calib_bn else PS("data")
        # check_vma=False: pallas_call (the use_pallas backends) has no
        # replication rule; every output is explicitly sharded anyway.
        fn = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                   in_specs=tuple(in_specs),
                                   out_specs=out_specs, check_vma=False))
        self._fns[key] = fn
        return fn

    # ---------------------------------------------------------------- pass --
    def run_pass(self, program: Tuple[LayerStep, ...], xs: List[np.ndarray],
                 bn: Optional[tuple], calibrate: bool = False):
        state = jnp.asarray(np.stack(self._pad_state(xs)))
        collected = []
        for i, step in enumerate(program):
            fn = self._step_fn(program, i, calibrate)
            args = [state, self._n_local]
            if step.bn_site is not None and not calibrate:
                mu, sd = bn[step.bn_site]
                args += [jnp.asarray(mu), jnp.asarray(sd)]
            if step.kind is not None:
                args += self._stacked[step.kind] + self._sched
            out = fn(*args)
            if step.bn_site is not None and calibrate:
                state, mu_stk, sd_stk = out
                collected.append((mu_stk[0], sd_stk[0]))
            else:
                state = out
            if step.kind is not None:
                # byte accounting from the STATIC schedule — correct even
                # when the jitted program never retraces (satellite fix).
                self.stats.add(step.tag, self.spmd.mesh_plan.payload_bytes(
                    step.payload_cols, step.payload_itemsize))
        full = np.asarray(state)
        blocks = [full[s, :pt.n_local] for s, pt in enumerate(self.parts)]
        return blocks, (tuple(collected) if calibrate else None)
