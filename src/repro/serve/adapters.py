"""Model-family adapters: the seam that makes :class:`ServeCore` generic.

The serving core (bucketed jit cache, high-water shape buckets, async
launch/finish, multi-bucket co-launch, trace hooks) is family-agnostic;
everything a model family actually computes lives behind a
:class:`ModelFamilyAdapter`:

  * **quantize** — params -> the bit-packed serving params the jitted body
    closes over (:func:`repro.serve.session_core.quantize_family` for GNNs,
    :func:`repro.quant.binary_linear.quantize_params` for token models);
  * **serve_body** — the TRACED forward/step program: called inside the
    core's jitted ``_serve`` with the staged operands, returns the launch
    result pytree. For GNNs this is rebuild-FRDC + ``family_forward`` +
    seed-row crop; for token models one chunk of exact single-token
    ``decode_step`` bodies scanned under teacher forcing;
  * **pad_operands** — bucket shaping: pad one extracted batch's operands
    up to the core's high-water pow2 marks so steady-state serving never
    recompiles (GNN: node + per-kind FRDC group water; token: the chunk
    width and cache length are already bucket-static, so it is identity);
  * **sub_operands / operand_like** — per-query state extraction: build
    the staged operands for an extracted closure, and the artifact
    template checkpoint restore validates against;
  * **state semantics** — the ``state`` argument threaded through
    ``launch(staged, state)`` and PINNED on a ``PreparedBatch`` at extract
    time (the calibration hook): the frozen BN tuple for GNNs, the
    ``(decode cache, previous-token)`` carry for token sessions;
  * **finish / trace_shape** — crop the launch result back to host answers
    and describe a staged batch's jit-cache shape key for the recompile
    watchdog.

``ServeCore`` takes an ``adapter=`` argument; when omitted it builds a
:class:`GNNAdapter` from its plan, so every pre-existing call site (and the
``batch_log`` replay oracle) is bitwise unchanged — the GNN body here IS
the old ``ServeCore._serve_one`` body, moved verbatim.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core import frdc
from . import session_core


class ModelFamilyAdapter:
    """Contract one model family implements to ride the serving core.

    ``kind`` namespaces the family in metrics/trace exports (the ``family``
    label on every Prometheus series and watchdog event).
    """

    kind = "?"

    # -- params ------------------------------------------------------------
    def quantize(self, params):
        """Dense params -> the serving params the jitted body closes over."""
        raise NotImplementedError

    # -- traced program ----------------------------------------------------
    def serve_body(self, core, x, state, operands, seeds):
        """The traced launch body. ``x``/``seeds`` are the staged dense
        arrays, ``operands`` the (padded) per-batch operand dict, ``state``
        the pinned calibration/carry pytree. Returns the launch result."""
        raise NotImplementedError

    # -- bucket shaping ----------------------------------------------------
    def pad_operands(self, core, operands, n_sub):
        """Pad one batch's operands to the core's high-water buckets;
        returns ``(n_pad, padded_operands)``. Must be monotone in the
        water marks — staging order, not launch order, is what the
        zero-steady-state-recompile guarantee keys on."""
        raise NotImplementedError

    # -- per-query state extraction ---------------------------------------
    def sub_operands(self, *args, **kw):
        """Build the operand dict for one extracted per-query closure."""
        raise NotImplementedError

    def operand_like(self):
        """Template pytree for checkpoint restore validation."""
        raise NotImplementedError

    # -- result / observability -------------------------------------------
    def finish(self, out_dev, staged) -> Any:
        """Block on one launch result and crop it to host answers."""
        raise NotImplementedError

    def trace_shape(self, staged) -> dict:
        """Shape key of one staged batch (recompile-watchdog payload)."""
        raise NotImplementedError

    def trace_shape_many(self, stageds: List) -> dict:
        """Shape key of a co-launched bucket set."""
        shapes = [self.trace_shape(s) for s in stageds]
        out: Dict[str, Any] = dict(multi=len(stageds))
        for k in (shapes[0] if shapes else {}):
            out[k] = [s[k] for s in shapes]
        return out


class GNNAdapter(ModelFamilyAdapter):
    """The GNN serving specifics, moved verbatim out of ``ServeCore``.

    Stateless w.r.t. the core (water marks live on each ``ServeCore``), so
    one adapter is shared by every shard core of a sharded session.
    """

    kind = "gnn"

    def __init__(self, plan: "session_core.SessionPlan"):
        self.plan = plan

    def quantize(self, params):
        return session_core.quantize_family(self.plan.family, params)

    def serve_body(self, core, x, state, operands, seeds):
        n_pad = x.shape[0]
        mats = {k: session_core.frdc_rebuild(v, n_pad, n_pad)
                for k, v in operands.items()}
        out = session_core.family_forward(self.plan, core.qparams, x, mats,
                                          use_pallas=core.use_pallas,
                                          bn_stats=state)
        return out[seeds]

    def pad_operands(self, core, operands, n_sub):
        n_pad = session_core.bucket_pow2(max(n_sub, core._n_water),
                                         core.NODE_BUCKET_FLOOR,
                                         core.node_cap)
        core._n_water = n_pad
        adjs = {}
        for k, m in operands.items():
            wkey = (n_pad, k)
            g_pad = max(core._g_water.get(wkey, 0),
                        session_core.bucket_pow2(m.n_groups,
                                                 core.GROUP_BUCKET_FLOOR))
            core._g_water[wkey] = g_pad
            adjs[k] = session_core.frdc_arrays(
                frdc.pad_frdc(m, n_pad, n_groups=g_pad))
        return n_pad, adjs

    def sub_operands(self, n_sub: int, sub_edges, dinv_sub):
        return session_core.sub_adjacency(self.plan.family, n_sub,
                                          sub_edges, dinv_sub)

    def operand_like(self):
        return session_core.adj_like(self.plan.family)

    def finish(self, out_dev, staged) -> np.ndarray:
        return np.asarray(out_dev)[:staged.n_seeds]

    def trace_shape(self, staged) -> dict:
        return dict(
            n_pad=staged.n_pad,
            groups={str(k): int(a["group_row"].shape[0])
                    for k, a in staged.adjs.items()})


class TokenAdapter(ModelFamilyAdapter):
    """Autoregressive token serving for the binary transformer / SSM stack.

    One launch runs ONE CHUNK of the decode program: ``chunk`` exact
    single-token :func:`repro.models.transformer.decode_step` bodies scanned
    under teacher forcing — global step ``p`` consumes the slot's prompt
    token while ``p < len`` and its own previous argmax after — and each
    step's argmax is the slot's generated-token stream. Scanning the exact
    step bodies (never the O(T^2) chunked prefill paths) keeps the served
    stream BITWISE identical to a python loop of ``jit(decode_step)``; the
    session chains chunk launches by threading the ``(cache, prev)`` carry,
    so the whole decode stays async on device.

    Shape discipline: the launch operands are the (B, chunk) prompt slice
    (zero-padded), the (B,) prompt lengths, and the chunk's traced base
    position — all static-shaped, so every chunk of every batch hits ONE
    jit entry. The only growable shape is the decode-cache length, bucketed
    by the core's pow2 high-water mark (``pad_operands``): zero steady-state
    recompiles across varied prompt/decode lengths once warmup sets the
    water.

    ``kind`` namespaces metrics/traces: "ssm" when the config's block
    pattern contains any recurrent block (mamba / rwkv, including hybrids),
    else "transformer".
    """

    SSM_BLOCKS = ("mamba", "mamba_attn", "rwkv")

    def __init__(self, cfg):
        if getattr(cfg, "is_encdec", False):
            raise ValueError(
                "encoder-decoder configs need an encoded memory per request "
                "and are not servable through the token session")
        self.cfg = cfg
        pattern = cfg.block_pattern()
        self.kind = ("ssm" if any(k in self.SSM_BLOCKS for k in pattern)
                     else "transformer")

    def quantize(self, params):
        from ..quant.binary_linear import quantize_params
        return quantize_params(params)

    def init_state(self, batch: int, cache_len: int) -> dict:
        """Fresh decode carry for one batch: the KV/recurrent caches plus
        the previous-argmax feedback token (device work — built at LAUNCH,
        never in the extract stage)."""
        from ..models import transformer
        return {"cache": transformer.init_cache(self.cfg, batch, cache_len),
                "prev": jnp.zeros((batch,), jnp.int32)}

    def serve_body(self, core, x, state, operands, seeds):
        from ..models import transformer
        cfg = self.cfg
        lens = seeds                           # (B,) prompt lengths
        pos0 = jnp.asarray(operands["base"]["pos0"], jnp.int32)

        def body(carry, xs):
            cache, prev = carry
            tok_p, p = xs
            tok = jnp.where(p < lens, tok_p, prev)
            logits, cache = transformer.decode_step(
                core.qparams, cfg, cache, tok[:, None], p)
            nxt = jnp.argmax(logits[:, 0, :cfg.vocab],
                             axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt

        q = x.shape[1]
        steps = pos0 + jnp.arange(q, dtype=jnp.int32)
        (cache, prev), gens = jax.lax.scan(
            body, (state["cache"], state["prev"]),
            (jnp.swapaxes(x, 0, 1), steps))
        return {"gens": jnp.swapaxes(gens, 0, 1),
                "state": {"cache": cache, "prev": prev}}

    def pad_operands(self, core, operands, n_sub):
        """Bucket the decode-cache length: ``n_sub`` is the batch's total
        step count, padded to the monotone pow2 water. A clamped cache
        would silently truncate the decode, so exceeding the cap raises."""
        if n_sub > core.node_cap:
            raise ValueError(
                f"decode needs {n_sub} cache positions but the session's "
                f"max_len is {core.node_cap}")
        n_pad = session_core.bucket_pow2(max(n_sub, core._n_water),
                                         core.NODE_BUCKET_FLOOR,
                                         core.node_cap)
        core._n_water = n_pad
        return n_pad, operands

    def sub_operands(self, pos0: int) -> dict:
        """Operand dict of one chunk: its base position, traced (values
        vary per chunk without touching the jit cache key)."""
        return {"base": {"pos0": np.int32(pos0)}}

    def operand_like(self) -> dict:
        return {"base": {"pos0": np.zeros((), np.int32)}}

    def finish(self, out_dev, staged) -> np.ndarray:
        return np.asarray(out_dev["gens"])

    def trace_shape(self, staged) -> dict:
        return dict(batch=int(staged.x_pad.shape[0]),
                    chunk=int(staged.x_pad.shape[1]))
