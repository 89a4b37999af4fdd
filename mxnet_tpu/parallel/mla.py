"""The latent stage of multi-head latent attention (MLA, arXiv:2405.04434):
what lies between an attention layer's input norm and its kernel.

h [B, S, D] is the normed hidden state. Queries and keys pass through
low-rank latents, each normed over its width:

    cq      = RMSNorm(h W_qa)                       [q_latent]
    q       = cq W_qb                               per head [nope | rope]
    [c | kp] = h W_kva                              [kv_latent | rope]
    kv      = RMSNorm(c) W_kvb                      per head [nope | v]

The rope part of k is ONE vector a position, shared by every head. The
rotary positions turn q's rope part and that vector (``transformer._attend``
does it, under ``mx.attn_proj``), then ``joined`` lays q = [q_nope | q_pe]
and k = [k_nope | k_pe] beside v for the kernel, at one head size
d = nope + rope, which is v's size too: the kernels take one head size.

A chip may hold a SHARE of the heads (``TransformerConfig.heads_held``): the
down-projections and their norms are computed alike for every head, the
up-projections, the kernel and the output rows for the heads held only, and
the partial output goes on to the next layer as an expert share's does.

No Pallas kernel: the stage is XLA's, under the scope ``mx.mla_latent``.
``metrics()["mla"]`` says what the newest step traced holds and the least
bytes its stage moves.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import profiler as _profiler

__all__ = ["mla_leaves", "latents", "joined", "latent_bytes"]


def mla_leaves(cfg):
    """{leaf: (shape of one layer, fan_in or None for a norm's scale, spec
    of one layer)}: the stage's rows of ``transformer._layer_leaves`` and
    the output's, in the place of ``wq``, ``wk``, ``wv`` and ``wo``. The
    output's fan_in is that of all the model's heads."""
    D, h, lq, lkv = cfg.dim, cfg.heads_here, cfg.q_latent, cfg.kv_latent
    nope, rope, dv = cfg.nope_head, cfg.rope_head, cfg.head_dim
    return {"wq_a": ((D, lq), D, (None, None)),
            "q_a_norm": ((lq,), None, (None,)),
            "wq_b": ((lq, h, nope + rope), lq, (None, "tp", None)),
            "wkv_a": ((D, lkv + rope), D, (None, None)),
            "kv_a_norm": ((lkv,), None, (None,)),
            "wkv_b": ((lkv, h, nope + dv), lkv, (None, "tp", None)),
            "wo": ((h, dv, D), cfg.n_heads * dv, ("tp", None, None))}


def latents(h, lp, cfg, norm):
    """h: [B, S, D] normed; lp: ``mla_leaves``; ``norm(x, scale)`` the
    model's RMSNorm. -> (q_nope [B, S, h, nope], q_pe [B, S, h, rope],
    k_nope [B, S, h, nope], k_pe [B, S, 1, rope], v [B, S, h, v])."""
    nope, lkv = cfg.nope_head, cfg.kv_latent
    cq = norm(jnp.einsum("bsd,dl->bsl", h, lp["wq_a"]), lp["q_a_norm"])
    q = jnp.einsum("bsl,lhk->bshk", cq, lp["wq_b"])
    ckv = jnp.einsum("bsd,dl->bsl", h, lp["wkv_a"])
    kv = jnp.einsum("bsl,lhk->bshk", norm(ckv[..., :lkv], lp["kv_a_norm"]),
                    lp["wkv_b"])
    return (q[..., :nope], q[..., nope:], kv[..., :nope],
            ckv[..., None, lkv:], kv[..., nope:])


def joined(q_nope, q_pe, k_nope, k_pe):
    """q = [q_nope | q_pe] and k = [k_nope | k_pe], the one rope vector of
    k a position given to every head held."""
    k_pe = jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[3:])
    return (jnp.concatenate([q_nope, q_pe], axis=-1),
            jnp.concatenate([k_nope, k_pe], axis=-1))


def latent_bytes(cfg, batch, seq):
    """The least bytes the stage moves through HBM in a step, forward and
    backward over all attention layers, every array in the activations'
    type: the forward reads h and the stage's weights and writes q, k and v
    for the heads held; the backward reads h, the weights and the gradients
    of q, k and v and writes h's gradient and the weights'. The latents
    themselves need not leave the chip, and what the layer's recompute runs
    again is not in it."""
    weights = sum(math.prod(shape) for name, (shape, _, _)
                  in mla_leaves(cfg).items() if name != "wo")
    h, d = cfg.heads_here, cfg.head_dim
    tokens = batch * seq
    per_layer = tokens * (3 * cfg.dim + 2 * h * 3 * d) \
        + 3 * weights
    return jnp.dtype(cfg.dtype).itemsize * cfg.attn_layers * per_layer


# metrics()["mla"]: what the newest step traced holds
# mxlint: disable=MX003 (GIL-atomic stores while a step is traced; one writer, the tracing thread)
_MLA = {"layers": 0, "heads_held": 0, "q_latent": 0, "kv_latent": 0,
        "latent_bytes": 0}


def note(cfg, batch, seq):
    """Called while a step of ``cfg`` on [batch, seq] tokens is traced: a
    fact of the program and not a count, so no reset clears it."""
    _MLA.update(layers=cfg.attn_layers, heads_held=cfg.heads_here,
                q_latent=cfg.q_latent, kv_latent=cfg.kv_latent,
                latent_bytes=int(latent_bytes(cfg, batch, seq)))


def mla_stats():
    """``metrics()['mla']``: of the newest train step traced whose attention
    is latent: ``layers`` (attention layers), ``heads_held`` (the heads this
    chip computes), ``q_latent`` and ``kv_latent`` (the latents' widths),
    ``latent_bytes`` (the least bytes the step's ``mx.mla_latent`` stages
    move, ``latent_bytes``: what a trace's milliseconds there are read
    against). Noughts where no step has one."""
    return dict(_MLA)


_profiler.register_stats_provider("mla", mla_stats)
