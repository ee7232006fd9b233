"""Jitted dispatch layer over the Pallas kernels.

On TPU the kernels compile natively (``interpret=False``); on CPU they run in
interpret mode for correctness, but the pure-jnp ``repro.core`` paths are much
faster there — so dispatch prefers jnp off-TPU unless ``force_kernels`` is on
(tests set it to exercise the kernels).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.core import bitops, bspmm as bspmm_core
from repro.core.binarize import BinTensor
from repro.core.frdc import FRDCMatrix, TILE

from . import bmm_kernel, bspmm_kernel, fused_layer, pack_kernel

_FORCE_KERNELS = False


def force_kernels(on: bool = True) -> None:
    global _FORCE_KERNELS
    _FORCE_KERNELS = on


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_kernels() -> bool:
    return _FORCE_KERNELS or _on_tpu()


def check_fused(fused: bool) -> None:
    """Refuse the fused per-layer kernel on a TPU backend (session build).

    ``fused_layer.fused_call`` has no grid: it holds whole arrays in VMEM
    and gathers inside the kernel, which Mosaic cannot compile and a real
    graph cannot fit. It stays runnable in interpret mode off-TPU."""
    if fused and _on_tpu():
        raise NotImplementedError(
            "fused per-layer kernels (SessionPlan.fused) do not compile "
            "for a TPU: fused_layer.fused_call has no grid and gathers "
            "inside one whole-array kernel; build the session with "
            "fused=False (the unfused Pallas path serves the same bits)")


def kernels_active(use_pallas: bool = True) -> bool:
    """Whether a ``use_pallas`` request actually routes through Pallas
    (TPU backend, or ``force_kernels`` in tests)."""
    return use_pallas and _use_kernels()


def launch_stats(fn, *args) -> dict:
    """Trace ``fn(*args)`` and count its device-operation footprint.

    Returns ``dict(eqns=..., pallas_calls=...)`` where ``eqns`` is the
    number of jaxpr equations (recursing through control-flow/pjit
    sub-jaxprs, but treating each ``pallas_call`` as ONE opaque equation —
    its body is a single launch no matter how much math it folds in) and
    ``pallas_calls`` the number of Pallas launches among them. ``eqns`` is
    an upper bound on device dispatches before XLA fusion; the delta
    between the unfused and fused serve paths is the launches-per-layer
    reduction the fused kernels buy, measured on the ACTUAL traced
    program rather than asserted."""
    closed = jax.make_jaxpr(fn)(*args)

    def _jaxprs_in(v):
        if isinstance(v, jax.core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax.core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from _jaxprs_in(item)

    def _count(jaxpr):
        eqns = pallas = 0
        for eqn in jaxpr.eqns:
            eqns += 1
            if eqn.primitive.name == "pallas_call":
                pallas += 1
                continue                     # one launch, however big
            for v in eqn.params.values():
                for sub in _jaxprs_in(v):
                    e, p = _count(sub)
                    eqns += e
                    pallas += p
        return eqns, pallas

    eqns, pallas = _count(closed.jaxpr)
    return dict(eqns=eqns, pallas_calls=pallas)


def bmm_xnor(a_packed: jax.Array, b_packed: jax.Array, n_bits: int,
             binarize: bool = False) -> jax.Array:
    """Packed ±1 matmul; kernel on TPU, word-level jnp elsewhere."""
    if _use_kernels():
        return bmm_kernel.bmm_xnor(a_packed, b_packed, n_bits,
                                   binarize=binarize)
    out = bitops.bmm_xnor_words(a_packed, b_packed, n_bits)
    if binarize:
        return bitops.pack_bits(out >= 0, axis=-1)
    return out


def binarize_pack(x: jax.Array) -> jax.Array:
    if _use_kernels():
        return pack_kernel.binarize_pack(x)
    return bitops.sign_bits(x, axis=-1)


def bspmm_bits(adj: FRDCMatrix, x_packed: jax.Array, n_feat: int,
               binarize: bool = True,
               trinary_mode: str = "s3_two_popc") -> jax.Array:
    """FRDC trinary aggregation; returns (n_rows, ...) cropped."""
    if _use_kernels():
        out = bspmm_kernel.bspmm_bits(adj, x_packed, n_feat,
                                      binarize=binarize,
                                      trinary_mode=trinary_mode)
        return out[: adj.n_rows]
    xt = BinTensor(packed=x_packed, scale=jnp.ones((x_packed.shape[0], 1)),
                   n=n_feat)
    res = bspmm_core.bspmm(adj, xt, "BBB" if binarize else "BBF",
                           trinary_mode=trinary_mode)
    return res.packed if binarize else res


def _serve_fp_backend(adj: FRDCMatrix, x: jax.Array,
                      block_shape=None) -> jax.Array:
    """core.bspmm fp-stage hook: Pallas BSpMM.FB? with scales applied here
    (the kernel computes raw masked matmuls)."""
    xin = x
    if adj.col_scale is not None:
        xin = xin * adj.col_scale[:, None].astype(x.dtype)
    out = bspmm_kernel.bspmm_fp(adj, xin, block_shape=block_shape)
    out = out[: adj.n_rows]
    if adj.row_scale is not None:
        out = out * adj.row_scale[:, None].astype(out.dtype)
    return out


def _serve_bits_backend(adj: FRDCMatrix, x_packed: jax.Array,
                        trinary_mode: str, block_shape=None) -> jax.Array:
    """core.bspmm trinary-counts hook: Pallas BSpMM.BB? raw counts."""
    out = bspmm_kernel.bspmm_bits(adj, x_packed, binarize=False,
                                  trinary_mode=trinary_mode,
                                  block_shape=block_shape)
    return out[: adj.n_rows]


@contextlib.contextmanager
def serve_kernels(enabled: bool = True, block_shape=None,
                  fused: bool = False):
    """Route BSpMM aggregation through the Pallas kernels while active.

    The serving sessions enter this at jit TRACE time (``use_pallas``
    config flag), so the kernel calls are baked into the compiled serve
    executables. Off-TPU (and without ``force_kernels``) it is a no-op and
    the reference jnp path runs instead — the sessions' documented fallback.
    ``block_shape`` is the session plan's BSpMM block-shape selection
    (``SessionPlan.bspmm_block``), forwarded to every kernel call the
    context routes — the TPU block-shape tuning seam; None keeps the
    kernel-native defaults. Yields whether the kernels are actually active.

    ``fused=True`` installs the VALUE-level aggregation backends from
    :mod:`repro.kernels.fused_layer` instead of the standalone
    ``pallas_call`` kernels — the form a fused per-layer kernel BODY can
    trace (Pallas cannot nest launches). The caller is then responsible
    for wrapping each layer in ``fused_layer.fused_call`` so the whole
    layer compiles to one launch; results stay bitwise identical to the
    unfused kernels (the walks accumulate in kernel order).
    """
    if not (enabled and _use_kernels()):
        yield False
        return
    if fused:
        fp = functools.partial(fused_layer.agg_fp, block_shape=block_shape)
        bits = functools.partial(fused_layer.agg_counts,
                                 block_shape=block_shape)
    else:
        fp = functools.partial(_serve_fp_backend, block_shape=block_shape)
        bits = functools.partial(_serve_bits_backend, block_shape=block_shape)
    with bspmm_core.override_backends(fp=fp, bits=bits):
        yield True


def bspmm_fp(adj: FRDCMatrix, x: jax.Array) -> jax.Array:
    """FRDC fp aggregation (scales applied here, kernel does raw counts)."""
    if _use_kernels():
        return _serve_fp_backend(adj, x)
    return bspmm_core.bspmm(adj, x, "FBF")


# ---------------------------------------------------------------------------
# Explicit-backend serve aggregations (shard_map-safe)
# ---------------------------------------------------------------------------
# The ``serve_kernels`` context mutates module globals, which is fine for
# the single jit trace of a ServeCore forward but fragile inside shard_map
# bodies (the SPMD layer executor traces P-way programs whose retraces are
# not under the session's control). These two entry points take the backend
# choice as an ARGUMENT instead, so a shard_map body is a pure function of
# its inputs; Pallas runs natively per shard on TPU and in interpret mode
# elsewhere (the callers must build their shard_map with ``check_vma=False``
# when routing through the kernels — pallas_call has no replication rule).

def serve_fp(adj: FRDCMatrix, x: jax.Array,
             use_pallas: bool = False) -> jax.Array:
    """BSpMM.FBF for the layer executors: exact scaled fp aggregation."""
    if use_pallas and _use_kernels():
        return _serve_fp_backend(adj, x)
    return bspmm_core.bspmm(adj, x, "FBF")


def serve_counts(adj: FRDCMatrix, x_packed: jax.Array,
                 trinary_mode: str = bspmm_core.TRINARY_DEFAULT,
                 use_pallas: bool = False) -> jax.Array:
    """BSpMM.BB? raw trinary counts for the layer executors — the integer
    partial sums of the distributed binary-aggregation layer (they add
    EXACTLY across the intra/halo split)."""
    xp = bspmm_core._pad_rows(x_packed, TILE)
    if use_pallas and _use_kernels():
        return _serve_bits_backend(adj, xp, trinary_mode)
    return bspmm_core._spmm_bits(adj, xp, trinary_mode)


def serve_fp_pair(intra: FRDCMatrix, halo: FRDCMatrix, x_local: jax.Array,
                  x_remote: jax.Array, use_pallas: bool = False
                  ) -> jax.Array:
    """Distributed FBF layer aggregation:
    ``(intra_raw @ x_local + halo_raw @ x_remote) * row_scale``.

    Both matrices share the owning shard's row scale, and XLA's algebraic
    simplifier factors ``a*r + b*r`` into ``(a+b)*r`` inside fused programs
    — which changes fp rounding vs two eagerly-scaled partials. Applying
    the (identical) row scale ONCE after the add writes the factored form
    explicitly, so the eager host executor and the fused SPMD layer
    programs stay bit-identical."""
    y = serve_fp(intra._replace(row_scale=None), x_local, use_pallas) \
        + serve_fp(halo._replace(row_scale=None), x_remote, use_pallas)
    if intra.row_scale is not None:
        y = y * intra.row_scale[:, None].astype(y.dtype)
    return y
