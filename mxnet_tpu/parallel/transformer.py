"""Flagship distributed model: decoder-only transformer LM, mesh-native.

The reference's largest-scale story is ResNet-152 data-parallel on 256 GPUs
(ref: example/image-classification/README.md:309); its sequence story is
bucketed RNNs. This module is the modern capability equivalent: one
transformer whose training step composes EVERY parallelism axis —

  dp    batch                       (≙ kvstore data parallel)
  fsdp  sharded params/optimizer    (≙ server-held state, ZeRO)
  tp    Megatron column/row splits  (psum on row-parallel outputs)
  sp    ring attention over ICI     (context parallelism)
  pp    GPipe stages over 'pp'      (≙ group2ctx model parallelism)
  ep    MoE experts                 (GShard-style dense dispatch)

Two execution modes:
- GSPMD mode (pp=1): params carry PartitionSpecs, jit compiles, XLA inserts
  collectives. Attention can be 'local', 'ring' (shard_map ppermute ring)
  or 'ulysses' (all-to-all head swap).
- Explicit mode (pp>1): the whole step runs in one shard_map over
  (pp, dp, sp, tp) with hand-written psum/ppermute — the scaling-book
  recipe, stage-homogeneous GPipe with microbatching.

RoPE positions, RMSNorm, SwiGLU FFN: bf16-friendly, static shapes, scan
over layers (single compiled layer body, MXU-sized matmuls).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import jax.random as jr
from jax import lax

from jax.ad_checkpoint import checkpoint_name as _ckpt_name

from .. import profiler as _profiler
from ..base import getenv as _getenv
from .compat import NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention, blockwise_attention
from .ulysses import ulysses_attention_local
from . import expert as _expert
from .expert import moe_ffn

__all__ = ["TransformerConfig", "init_params", "apply", "loss_fn",
           "make_train_step", "param_specs", "ce_local_accum_active"]


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    ffn_hidden: int = 1376
    max_seq_len: int = 2048
    dtype: str = "float32"
    # parallelism
    attn_mode: str = "local"          # 'local' | 'ring' | 'ulysses' | 'blockwise'
    pp: int = 1                        # pipeline stages (>1 = explicit mode)
    n_microbatch: int = 1
    # MoE: every `moe_every`-th layer is an expert layer when num_experts > 0
    num_experts: int = 0
    moe_k: int = 2
    causal: bool = True
    # rematerialize each layer in backward (activation recompute): trades
    # ~1/3 more FLOPs for O(n_layers) less activation HBM, the standard
    # TPU trade (SURVEY §7: jax.checkpoint)
    remat: bool = True
    # selective remat: names of intermediates the backward may KEEP
    # instead of recomputing (jax save_only_these_names policy).
    # "ffn_prod" saves the gated-FFN product [B,S,ffn_hidden] — skips
    # recomputing the two up-projections (the biggest matmuls);
    # "attn_o" saves the attention output [B,S,D] — skips re-running
    # the flash forward kernel inside the backward. Empty = full remat.
    remat_save: tuple = ()
    # >1: compute the final projection + cross-entropy in this many
    # sequence chunks (sequential lax.map + per-chunk remat), so the
    # [B, S, vocab] f32 logits tensor never materializes — at 32k vocab
    # that saves GBs of HBM and is what lets batch 8 fit on one chip
    loss_chunks: int = 1
    # accumulate the chunked-CE unembedding gradient LOCALLY (shard_map
    # over the batch axes) and reduce it ONCE, instead of letting GSPMD
    # keep the all-reduce inside the chunk scan (the SCALING_r05
    # finding: AR-per-chunk adds (loss_chunks-1)*vocab*dim*4 wire bytes
    # per step, ~36% extra transformer bytes at 256 chips). Needs the
    # mesh passed to loss_fn/make_train_step; covers dp x sp x tp
    # layouts (tp-sharded vocab handled with a distributed logsumexp).
    # None = AUTO: on whenever the mesh shards the batch (dp*sp > 1),
    # loss_chunks > 1 and the shapes divide; True forces it (indivisible
    # shapes raise); False pins the plain chunked CE. The
    # MXTPU_CE_LOCAL_ACCUM env var ('auto'/'1'/'0', a compile-signature
    # token) overrides the auto default process-wide.
    ce_local_accum: Optional[bool] = None
    # -- what a decoder with grouped heads, windows and an expert share
    # needs; every default leaves the plain decoder's program as it is ------
    # key-value heads (None = n_heads): query head h reads head h // (H/G)
    n_kv_heads: Optional[int] = None
    # a head's size where it is not dim // n_heads
    head_size: Optional[int] = None
    # one period of attention kinds, "sliding" (a query sees the last
    # ``window`` keys) or "full"; the trunk scans over whole periods, the
    # body holding the period's layers in turn. () = every layer "full",
    # scanned one layer a step.
    layer_pattern: tuple = ()
    window: Optional[int] = None
    # attention kinds of the leading dense layers (gated FFN of width
    # ffn_hidden) that come before the scanned periods; only with a
    # layer_pattern. n_layers = len(dense_layers) + periods * len(pattern)
    dense_layers: tuple = ()
    rope_on: str = "all"               # 'all' | 'sliding': which kinds rotate
    norm_eps: float = 1e-6
    qk_norm: bool = False              # RMSNorm of q and k over a head
    attn_gate: bool = False            # attention output * sigmoid(h W_g)
    post_norms: bool = False           # a norm after attention and after FFN
    embed_scale: bool = False          # embedding * sqrt(dim)
    # the scanned layers' FFN as one chip's SHARE of an expert layer
    # (expert.moe_share): set moe_hidden (the experts' width); the layer
    # routes over num_experts, holds experts_held = (first, count) of them
    # (None = all), moe_shared shared experts of the same width, three-
    # matrix SiLU experts, sigmoid scores, no drops, no auxiliary loss
    moe_hidden: Optional[int] = None
    experts_held: Optional[tuple] = None
    moe_shared: int = 0
    route_scale: float = 1.0

    @property
    def head_dim(self):
        return self.head_size or self.dim // self.n_heads

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def expert_share(self):
        """(first, count) of the experts held, where the scanned layers
        are shares of an expert layer; else None."""
        if self.moe_hidden is None or self.num_experts <= 0:
            return None
        return tuple(self.experts_held or (0, self.num_experts))

    @property
    def periods(self):
        """Whole periods of ``layer_pattern`` after the dense layers."""
        n, p = self.n_layers - len(self.dense_layers), len(self.layer_pattern)
        if p == 0 or n <= 0 or n % p:
            raise ValueError(
                "n_layers=%d is not %d dense layers and whole periods of %d"
                % (self.n_layers, len(self.dense_layers), p))
        return n // p


def _rms_norm(x, scale, eps):
    """``eps`` is the configuration's (``TransformerConfig.norm_eps``)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rope(x, positions):
    """Rotary position embedding. x: [B, H, S, D_h], positions: [S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return rot.astype(x.dtype)


def init_params(key, cfg: TransformerConfig):
    """Param pytree. Layer params are STACKED on a leading axis: [L, ...]
    in GSPMD mode, [pp, L/pp, ...] in explicit pipeline mode — the leading
    axis is scanned (one compiled layer body) and, for pp, mesh-sharded."""
    dt = jnp.dtype(cfg.dtype)
    D, H, Dh, F = cfg.dim, cfg.n_heads, cfg.head_dim, cfg.ffn_hidden
    L = cfg.n_layers
    keys = jr.split(key, 8)

    def norm(k, shape, fan_in):
        return (jr.normal(k, shape) * (fan_in ** -0.5)).astype(dt)

    if cfg.layer_pattern:
        return _init_pattern_params(key, cfg, norm)

    layer = {
        "ln1": jnp.ones((L, D), dt),
        "wq": norm(keys[0], (L, D, H, Dh), D),
        "wk": norm(keys[1], (L, D, H, Dh), D),
        "wv": norm(keys[2], (L, D, H, Dh), D),
        "wo": norm(keys[3], (L, H, Dh, D), H * Dh),
        "ln2": jnp.ones((L, D), dt),
        "w_gate": norm(keys[4], (L, D, F), D),
        "w_up": norm(keys[5], (L, D, F), D),
        "w_down": norm(keys[6], (L, F, D), F),
    }
    if cfg.num_experts > 0:
        E = cfg.num_experts
        ek = jr.split(keys[7], 4)
        layer["moe_router"] = norm(ek[0], (L, D, E), D)
        layer["moe_w1"] = norm(ek[1], (L, E, D, F), D)
        layer["moe_w2"] = norm(ek[2], (L, E, F, D), F)
    if cfg.pp > 1:
        assert L % cfg.pp == 0, "n_layers must divide pp"
        layer = {k: v.reshape((cfg.pp, L // cfg.pp) + v.shape[1:])
                 for k, v in layer.items()}
    emb_key, out_key = jr.split(jr.fold_in(key, 99))
    return {
        "embed": norm(emb_key, (cfg.vocab_size, D), D) * (D ** 0.5),
        "layers": layer,
        "ln_f": jnp.ones((D,), dt),
        "w_out": norm(out_key, (D, cfg.vocab_size), D),
    }


def _pattern_leaves(cfg, experts):
    """{leaf: (shape of one layer, fan_in or None for a norm's scale or 0
    for the router's bias, spec of one layer)} of a pattern model's dense
    (``experts`` False) or scanned layer."""
    D, H, G, Dh = cfg.dim, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    out = {"ln1": ((D,), None, (None,)),
           "wq": ((D, H, Dh), D, (None, "tp", None)),
           "wk": ((D, G, Dh), D, (None, "tp", None)),
           "wv": ((D, G, Dh), D, (None, "tp", None)),
           "wo": ((H, Dh, D), H * Dh, ("tp", None, None)),
           "ln2": ((D,), None, (None,))}
    if cfg.attn_gate:
        out["w_attn_gate"] = ((D, H, Dh), D, (None, "tp", None))
    if cfg.qk_norm:
        out["q_norm"] = out["k_norm"] = ((Dh,), None, (None,))
    if cfg.post_norms:
        out["ln1_post"] = out["ln2_post"] = ((D,), None, (None,))
    share = cfg.expert_share if experts else None
    if share is None:
        F = cfg.ffn_hidden
        out.update({"w_gate": ((D, F), D, (None, "tp")),
                    "w_up": ((D, F), D, (None, "tp")),
                    "w_down": ((F, D), F, ("tp", None))})
        return out
    E, Fm, held = cfg.num_experts, cfg.moe_hidden, share[1]
    out.update({"moe_router": ((D, E), D, (None, None)),
                "moe_bias": ((E,), 0, (None,)),
                "moe_w_gate": ((held, D, Fm), D, (None, None, None)),
                "moe_w_up": ((held, D, Fm), D, (None, None, None)),
                "moe_w_down": ((held, Fm, D), Fm, (None, None, None))})
    if cfg.moe_shared:
        Fs = Fm * cfg.moe_shared
        out.update({"ws_gate": ((D, Fs), D, (None, "tp")),
                    "ws_up": ((D, Fs), D, (None, "tp")),
                    "ws_down": ((Fs, D), Fs, ("tp", None))})
    return out


def _init_pattern_params(key, cfg, norm):
    """Params of a pattern model: ``dense`` stacked [n_dense, ...] (where
    there are leading dense layers) and ``layers`` stacked [periods, P,
    ...], one row a period and one column a place in the pattern."""
    dt = jnp.dtype(cfg.dtype)
    D = cfg.dim

    def stack(lead, leaves, salt):
        out = {}
        for i, (name, (shape, fan_in, _)) in enumerate(leaves.items()):
            k = jr.fold_in(key, salt + i)
            if fan_in is None:
                out[name] = jnp.ones(lead + shape, dt)
            elif fan_in == 0:   # the router's bias: a buffer, N(0, 0.01^2)
                out[name] = (jr.normal(k, lead + shape) * 0.01).astype(dt)
            else:
                out[name] = norm(k, lead + shape, fan_in)
        return out

    emb_key, out_key = jr.split(jr.fold_in(key, 99))
    embed = norm(emb_key, (cfg.vocab_size, D), D)
    params = {
        "embed": embed if cfg.embed_scale else embed * (D ** 0.5),
        "layers": stack((cfg.periods, len(cfg.layer_pattern)),
                        _pattern_leaves(cfg, True), 1000),
        "ln_f": jnp.ones((D,), dt),
        "w_out": norm(out_key, (D, cfg.vocab_size), D),
    }
    if cfg.dense_layers:
        params["dense"] = stack((len(cfg.dense_layers),),
                                _pattern_leaves(cfg, False), 2000)
    return params


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs matching init_params structure (GSPMD mode).
    Column-parallel on heads/ffn over 'tp'; fsdp composes by sharding the
    layer-stack axis? No — fsdp shards the largest non-tp dim via
    sharding.fsdp rules; here we give the Megatron TP layout."""
    if cfg.layer_pattern:
        of = lambda lead, experts: {  # noqa: E731
            n: P(*(lead + spec))
            for n, (_, _, spec) in _pattern_leaves(cfg, experts).items()}
        specs = {"embed": P("tp", None), "layers": of((None, None), True),
                 "ln_f": P(None), "w_out": P(None, "tp")}
        if cfg.dense_layers:
            specs["dense"] = of((None,), False)
        return specs
    lead = ("pp",) if cfg.pp > 1 else (None,)
    lead = lead + ((None,) if cfg.pp > 1 else ())

    def ls(*rest):  # layer-stacked spec
        return P(*(lead + rest))

    layer = {
        "ln1": ls(None),
        "wq": ls(None, "tp", None),
        "wk": ls(None, "tp", None),
        "wv": ls(None, "tp", None),
        "wo": ls("tp", None, None),
        "ln2": ls(None),
        "w_gate": ls(None, "tp"),
        "w_up": ls(None, "tp"),
        "w_down": ls("tp", None),
    }
    if cfg.num_experts > 0:
        layer["moe_router"] = ls(None, None)
        layer["moe_w1"] = ls("ep", None, "tp")
        layer["moe_w2"] = ls("ep", "tp", None)
    if cfg.pp > 1:
        # explicit mode indexes embed/w_out with global token ids inside the
        # shard_map body, so they stay replicated across tp
        embed_spec, out_spec = P(None, None), P(None, None)
    else:
        embed_spec, out_spec = P("tp", None), P(None, "tp")
    return {
        "embed": embed_spec,
        "layers": layer,
        "ln_f": P(None),
        "w_out": out_spec,
    }


# --------------------------------------------------------------------------
# GSPMD mode forward (pp == 1)
# --------------------------------------------------------------------------

def _attention(cfg, mesh, q, k, v, positions, window=None):
    """q: [B, S, H, Dh], k/v: [B, S, G, Dh] -> [B, S, H, Dh]. Global arrays
    (GSPMD mode). ``window``: this layer's queries see that many keys."""
    qt = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, S, Dh]
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if (window is not None or kt.shape[1] != qt.shape[1]) \
            and cfg.attn_mode != "local":
        raise NotImplementedError(
            "grouped key-value heads and windows run in attn_mode='local' "
            "only (the flash kernels); attn_mode=%r has neither"
            % cfg.attn_mode)
    if cfg.attn_mode == "ring_flash" and mesh is not None:
        # inter-chip ppermute ring x intra-chip Pallas flash blocks,
        # differentiable both directions (parallel/ring_flash.py)
        from .ring_flash import ring_flash_self_attention
        ot = ring_flash_self_attention(qt, kt, vt, mesh, axis_name="sp",
                                       causal=cfg.causal)
    elif cfg.attn_mode == "ring" and mesh is not None:
        from .ring_attention import ring_self_attention
        ot = ring_self_attention(qt, kt, vt, mesh, axis_name="sp",
                                 causal=cfg.causal)
    elif cfg.attn_mode == "ulysses" and mesh is not None:
        from .ulysses import ulysses_attention
        ot = ulysses_attention(qt, kt, vt, mesh, axis_name="sp",
                               causal=cfg.causal)
    elif cfg.attn_mode == "blockwise":
        ot = blockwise_attention(qt, kt, vt, causal=cfg.causal)
    else:
        # local full attention: Pallas flash kernel on TPU (O(S·D) HBM
        # traffic), jnp reference elsewhere — see pallas_kernels/
        from ..pallas_kernels import flash_attention
        S = qt.shape[2]
        if S % 128 == 0:
            attend = functools.partial(flash_attention, causal=cfg.causal)
            if window is not None:
                attend = functools.partial(attend, window=window)
            sizes = _mesh_sizes(mesh)
            if any(n > 1 for n in sizes.values()):
                # GSPMD cannot partition a Mosaic kernel ("wrap the call
                # in a shard_map", says the TPU lowering — the CPU mesh,
                # where the jnp reference runs, never showed it).
                # Attention is independent per sequence and per head:
                # each device runs the kernel on its batch ('dp') and
                # head ('tp') shard; an axis that does not divide stays
                # replicated, as does the sequence.
                from .compat import shard_map
                spec = P(
                    "dp" if qt.shape[0] % sizes.get("dp", 1) == 0 else None,
                    "tp" if qt.shape[1] % sizes.get("tp", 1) == 0
                    and kt.shape[1] % sizes.get("tp", 1) == 0 else None,
                    None, None)
                attend = shard_map(attend, mesh, in_specs=(spec,) * 3,
                                   out_specs=spec, check_vma=False)
            ot = attend(qt, kt, vt)
        else:
            from ..pallas_kernels.flash_attention import attention_reference
            ot = attention_reference(qt, kt, vt, causal=cfg.causal,
                                     window=window)
    return jnp.transpose(ot, (0, 2, 1, 3))


def _mesh_sizes(mesh):
    """{axis: size} of a DeviceMesh / jax Mesh; {} for no mesh."""
    if mesh is None:
        return {}
    return {a: int(n) for a, n in
            dict(getattr(mesh, "mesh", mesh).shape).items()}


def _layer_body(cfg, mesh, positions, x, lp, kind="full"):
    """One transformer layer. x: [B, S, D]; lp: this layer's params, which
    say what its feed-forward is (dense, the GShard experts, or a share of
    an expert layer); ``kind``: its attention, "full" or "sliding".
    -> (x, aux): the GShard load-balance loss, or the share's counters."""
    eps, sliding = cfg.norm_eps, kind == "sliding"
    with jax.named_scope("mx.attn_proj"):
        h = _rms_norm(x, lp["ln1"], eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.attn_gate:
            gate = jnp.einsum("bsd,dhk->bshk", h, lp["w_attn_gate"])
        if cfg.qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
        if cfg.rope_on == "all" or sliding:
            q = jnp.transpose(_rope(jnp.transpose(q, (0, 2, 1, 3)),
                                    positions), (0, 2, 1, 3))
            k = jnp.transpose(_rope(jnp.transpose(k, (0, 2, 1, 3)),
                                    positions), (0, 2, 1, 3))
    with jax.named_scope("mx.flash"):
        o = _ckpt_name(_attention(cfg, mesh, q, k, v, positions,
                                  cfg.window if sliding else None), "attn_o")
    with jax.named_scope("mx.attn_out"):
        if cfg.attn_gate:
            o = o * jax.nn.sigmoid(gate)
        a = jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        if cfg.post_norms:
            a = _rms_norm(a, lp["ln1_post"], eps)
        x = x + a
    with jax.named_scope("mx.ffn"):
        h = _rms_norm(x, lp["ln2"], eps)
        if "moe_w_gate" in lp:
            shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
                if "ws_gate" in lp else None
            y, aux = _expert.moe_share(
                h, lp["moe_router"], lp["moe_bias"], lp["moe_w_gate"],
                lp["moe_w_up"], lp["moe_w_down"], shared, k=cfg.moe_k,
                first=cfg.expert_share[0], route_scale=cfg.route_scale)
        elif cfg.num_experts > 0 and cfg.moe_hidden is None:
            y, aux = moe_ffn(h, lp["moe_router"], lp["moe_w1"],
                             lp["moe_w2"], k=cfg.moe_k)
            return x + y, aux
        else:
            g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]))
            u = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
            prod = _ckpt_name(g * u, "ffn_prod")
            y, aux = jnp.einsum("bsf,fd->bsd", prod, lp["w_down"]), 0.0
        if cfg.post_norms:
            y = _rms_norm(y, lp["ln2_post"], eps)
        return x + y, aux


def apply(params, tokens, cfg: TransformerConfig, mesh=None,
          return_aux=False):
    """Forward: tokens [B, S] int32 -> logits [B, S, V]. GSPMD mode.
    With return_aux, also returns the summed MoE load-balance loss."""
    x, aux = _hidden(params, tokens, cfg, mesh)
    with jax.named_scope("mx.head_ce"):
        logits = jnp.einsum("bsd,dv->bsv", x, params["w_out"])
    if return_aux:
        return logits, aux
    return logits


def _remat_policy(cfg):
    """None = recompute everything; with cfg.remat_save, keep the named
    intermediates (save_only_these_names) so the backward skips their
    producers — selective remat, the memory/recompute dial."""
    if not cfg.remat_save:
        return None
    return jax.checkpoint_policies.save_only_these_names(*cfg.remat_save)


def _hidden(params, tokens, cfg, mesh):
    """Trunk forward up to (but excluding) the output projection;
    returns (x [B,S,D], summed aux)."""
    with jax.named_scope("mx.embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embed_scale:
            x = (x * (cfg.dim ** 0.5)).astype(x.dtype)
    positions = jnp.arange(tokens.shape[1])
    if cfg.layer_pattern:
        return _hidden_pattern(params, x, positions, cfg, mesh)

    def body(x, lp):
        x, aux = _layer_body(cfg, mesh, positions, x, lp)
        return x, aux

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    with jax.named_scope("mx.layer"):
        x, auxs = lax.scan(body, x, params["layers"])
    with jax.named_scope("mx.head_ce"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, jnp.sum(auxs)


def _hidden_pattern(params, x, positions, cfg, mesh):
    """The trunk of a pattern model: the leading dense layers, then a scan
    over whole periods whose body holds the period's layers in turn, each
    under the layer remat. -> (x, the expert shares' counters summed, or
    0.0 where no layer is a share)."""
    counted = cfg.expert_share is not None
    zero = jnp.zeros(len(_expert.MOE_STATS), jnp.int32)

    def layer(kind):
        def one(x, lp):
            x, aux = _layer_body(cfg, mesh, positions, x, lp, kind)
            return x, (aux if counted and "moe_w_gate" in lp else zero)
        return jax.checkpoint(one, policy=_remat_policy(cfg)) \
            if cfg.remat else one

    at = lambda tree, i: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a[i], tree)

    def period(x, lp):
        stats = zero
        for j, kind in enumerate(cfg.layer_pattern):
            x, one = layer(kind)(x, at(lp, j))
            stats = _expert.merge_stats(stats, one)
        return x, stats

    with jax.named_scope("mx.layer"):
        for i, kind in enumerate(cfg.dense_layers):
            x, _ = layer(kind)(x, at(params["dense"], i))
        x, stats = lax.scan(period, x, params["layers"])
    with jax.named_scope("mx.head_ce"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    if not counted:
        return x, 0.0
    return x, _expert.sum_stats(stats)


def _chunked_ce(x, w_out, targets, n_chunks):
    """Mean token NLL with the vocab projection done per sequence chunk.

    lax.map runs chunks sequentially, and jax.checkpoint makes the
    backward recompute each chunk's logits instead of saving them, so
    peak HBM holds ONE [B, S/n, V] f32 tile instead of the full
    [B, S, V] logits (2+ GB at 32k vocab, batch 8, seq 2048)."""
    B, S, D = x.shape
    C = S // n_chunks
    xc = jnp.swapaxes(x.reshape(B, n_chunks, C, D), 0, 1)
    tc = jnp.swapaxes(targets.reshape(B, n_chunks, C), 0, 1)

    @jax.checkpoint
    def chunk_nll(args):
        xi, ti = args
        logits = jnp.einsum("bcd,dv->bcv", xi, w_out,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    return jnp.sum(lax.map(chunk_nll, (xc, tc))) / (B * S)


def _chunked_ce_local(x, w_out, targets, n_chunks, mesh):
    """Chunked CE with LOCAL unembedding-gradient accumulation — the
    SCALING_r05 fix. The plain ``_chunked_ce`` under GSPMD keeps the
    ``dw_out`` all-reduce INSIDE the chunk loop (scan carries must hold
    a concrete sharding, so every chunk's batch-sharded partial sum is
    reduced before the add): (loss_chunks-1) extra vocab*dim reductions
    per step. Running the loop inside ``shard_map`` makes the partial
    sums per-device values no sharding rule touches; the chunk scan
    accumulates ``dw_out`` locally and the ONE reduction happens at the
    shard_map boundary (the transpose of w_out's replicated-over-dp/sp
    in_spec). With vocab sharded over 'tp', logsumexp and the target
    gather run distributed (pmax/psum over 'tp')."""
    from .compat import shard_map
    raw = getattr(mesh, "mesh", mesh)
    sizes = _mesh_sizes(mesh)
    sp, tp = sizes.get("sp", 1), sizes.get("tp", 1)
    B, S, _ = x.shape
    if (S // sp) % n_chunks != 0:
        raise ValueError(
            "loss_chunks=%d does not divide the local sequence length "
            "%d (seq %d / sp %d)" % (n_chunks, S // sp, S, sp))

    def body(xl, wl, tl):
        b, s_l, d = xl.shape
        C = s_l // n_chunks
        xc = jnp.swapaxes(xl.reshape(b, n_chunks, C, d), 0, 1)
        tc = jnp.swapaxes(tl.reshape(b, n_chunks, C), 0, 1)
        Vl = wl.shape[-1]

        @jax.checkpoint
        def chunk_nll(args):
            xi, ti = args
            logits = jnp.einsum("bcd,dv->bcv", xi, wl,
                                preferred_element_type=jnp.float32)
            if tp > 1:
                # distributed logsumexp over the tp-sharded vocab; the
                # max shift is numerics-only (its gradient contribution
                # is exactly zero), so stop_gradient keeps it out of the
                # backward — pmax has no differentiation rule anyway
                m = lax.pmax(
                    lax.stop_gradient(jnp.max(logits, axis=-1)), "tp")
                s = lax.psum(
                    jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                    "tp")
                lse = jnp.log(s) + m
                base = lax.axis_index("tp") * Vl
                loc = ti - base
                inb = (loc >= 0) & (loc < Vl)
                got = jnp.take_along_axis(
                    logits, jnp.clip(loc, 0, Vl - 1)[..., None],
                    axis=-1)[..., 0]
                tgt = lax.psum(jnp.where(inb, got, 0.0), "tp")
            else:
                lse = jax.nn.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(logits, ti[..., None],
                                          axis=-1)[..., 0]
            return jnp.sum(lse - tgt)

        total = jnp.sum(lax.map(chunk_nll, (xc, tc)))
        for ax in ("dp", "sp"):
            if sizes.get(ax, 1) > 1:
                total = lax.psum(total, ax)
        return total

    total = shard_map(
        body, raw,
        in_specs=(P("dp", "sp", None), P(None, "tp"), P("dp", "sp")),
        out_specs=P(), check_vma=False)(x, w_out, targets)
    return total / (B * S)


_WARNED = set()  # mxlint: disable=MX003 (warn-once dedup keys; worst case under a race is one duplicate warning)


def _warn_once(key, msg):
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def ce_local_accum_active(cfg, mesh, batch, seq):
    """Whether this (cfg, mesh, batch shape) runs the single-reduction
    chunked CE (``_chunked_ce_local``). ``cfg.ce_local_accum=None``
    AUTO-selects it whenever the mesh shards the batch (dp*sp > 1 — the
    only case the AR-per-chunk pattern costs wire bytes) and the shapes
    divide; an explicit ``True`` forces it (indivisible shapes keep the
    hard error from ``_chunked_ce_local``); ``False`` pins the plain
    path. ``MXTPU_CE_LOCAL_ACCUM`` ('auto' default / '1' / '0', a
    compile-signature token) is the process-wide override — before this
    auto-select, real trainer runs silently paid the +36%-at-256-chips
    wire bytes the local-accum fix already kills (SCALING_r05)."""
    if cfg.loss_chunks <= 1 or mesh is None:
        return False
    env = str(_getenv("MXTPU_CE_LOCAL_ACCUM", "auto")).lower()
    if env in ("0", "off", "false") or cfg.ce_local_accum is False:
        return False
    forced = cfg.ce_local_accum is True or env in ("1", "on", "true")
    sizes = _mesh_sizes(mesh)
    dp, sp = sizes.get("dp", 1), sizes.get("sp", 1)
    if not forced and dp * sp <= 1:
        return False  # no batch-sharded partial sums -> nothing to save
    divisible = (int(batch) % max(dp, 1) == 0
                 and int(seq) % max(sp, 1) == 0
                 and (int(seq) // max(sp, 1)) % cfg.loss_chunks == 0)
    if not divisible and cfg.ce_local_accum is not True:
        # auto must not turn a shape quirk into a crash — but it also
        # must not SILENTLY hand back the AR-per-chunk bytes
        _warn_once(
            "ce-local-accum-indivisible",
            "ce_local_accum auto-select declined: batch=%d/seq=%d do "
            "not divide over dp=%d/sp=%d with loss_chunks=%d; this "
            "step pays the per-chunk unembedding-grad all-reduce "
            "(+(loss_chunks-1)*vocab*dim*4 wire bytes)"
            % (batch, seq, dp, sp, cfg.loss_chunks))
        return False
    return True


def loss_fn(params, tokens, targets, cfg, mesh=None, aux_weight=0.01):
    loss, aux = _loss_and_aux(params, tokens, targets, cfg, mesh)
    if cfg.num_experts > 0 and cfg.moe_hidden is None:
        loss = loss + aux_weight * aux  # GShard load-balance pressure
    return loss


def _loss_and_aux(params, tokens, targets, cfg, mesh):
    """-> (mean token NLL, what the trunk gave beside the hidden state: the
    GShard layers' load-balance loss, or an expert share's counters)."""
    if cfg.loss_chunks > 1:
        if tokens.shape[1] % cfg.loss_chunks != 0:
            # a silent full-logits fallback would re-materialize the
            # [B,S,V] tensor loss_chunks exists to avoid (and OOM)
            raise ValueError(
                "loss_chunks=%d does not divide seq_len=%d; pick a "
                "divisor or set loss_chunks=1"
                % (cfg.loss_chunks, tokens.shape[1]))
        x, aux = _hidden(params, tokens, cfg, mesh)
        local = ce_local_accum_active(cfg, mesh, tokens.shape[0],
                                      tokens.shape[1])
        with jax.named_scope("mx.head_ce"):
            if local:
                loss = _chunked_ce_local(x, params["w_out"], targets,
                                         cfg.loss_chunks, mesh)
            else:
                loss = _chunked_ce(x, params["w_out"], targets,
                                   cfg.loss_chunks)
    else:
        logits, aux = apply(params, tokens, cfg, mesh, return_aux=True)
        with jax.named_scope("mx.head_ce"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
    return loss, aux


# --------------------------------------------------------------------------
# Explicit SPMD mode (pp > 1): whole step inside one shard_map
# --------------------------------------------------------------------------

def _layer_body_local(cfg, positions, x, lp):
    """Per-device layer body used inside shard_map: tp dims of lp are LOCAL
    shards; row-parallel outputs need psum over 'tp'. Sequence dim of x is
    the local 'sp' shard; attention uses the ppermute ring."""
    with jax.named_scope("mx.attn_proj"):
        h = _rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = jnp.transpose(_rope(jnp.transpose(q, (0, 2, 1, 3)), positions),
                          (0, 2, 1, 3))
        kq = jnp.transpose(_rope(jnp.transpose(k, (0, 2, 1, 3)), positions),
                           (0, 2, 1, 3))
    with jax.named_scope("mx.flash"):
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(kq, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        ot = ring_attention(qt, kt, vt, "sp", causal=cfg.causal,
                            q_offset=positions[0])
        o = jnp.transpose(ot, (0, 2, 1, 3))
    with jax.named_scope("mx.attn_out"):
        attn_out = lax.psum(jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), "tp")
        x = x + attn_out
    with jax.named_scope("mx.ffn"):
        h = _rms_norm(x, lp["ln2"], cfg.norm_eps)
        g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]))
        u = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
        ffn_out = lax.psum(
            jnp.einsum("bsf,fd->bsd", g * u, lp["w_down"]), "tp")
        return x + ffn_out


def _pipeline_forward_local(cfg, params, tokens):
    """Inside shard_map over (pp, dp, sp, tp). tokens: [B_local, S_local].
    GPipe fill-drain over microbatches (pipeline.gpipe_loop); activations
    rotate over 'pp'."""
    from .pipeline import gpipe_loop
    sp_idx = lax.axis_index("sp")
    B, S_local = tokens.shape
    M = cfg.n_microbatch
    assert B % M == 0
    mb = B // M
    positions = sp_idx * S_local + jnp.arange(S_local)

    with jax.named_scope("mx.embed"):
        x_all = jnp.take(params["embed"], tokens, axis=0)   # [B, S_l, D]
    x_mb = x_all.reshape(M, mb, S_local, cfg.dim)

    stage_params = jax.tree_util.tree_map(lambda p: p[0], params["layers"])

    def stage_fn(x):
        def body(x, lp):
            return _layer_body_local(cfg, positions, x, lp), None
        with jax.named_scope("mx.layer"):
            x, _ = lax.scan(body, x, stage_params)
        return x

    outs = gpipe_loop(stage_fn, x_mb, "pp")
    x = outs.reshape(B, S_local, cfg.dim)
    with jax.named_scope("mx.head_ce"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["w_out"])
    return logits


def _pipeline_loss_local(cfg, params, tokens, targets):
    logits = _pipeline_forward_local(cfg, params, tokens)
    with jax.named_scope("mx.head_ce"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        # mean over local tokens, then over dp & sp shards
        return lax.pmean(lax.pmean(jnp.mean(ll), "dp"), "sp") * -1.0


# --------------------------------------------------------------------------
# Train-step builders
# --------------------------------------------------------------------------

def _sgd_momentum(params, mom, grads, learning_rate):
    """The step's one optimizer: momentum 0.9, in the state's own type.
    -> (new params, new momentum)."""
    with jax.named_scope("mx.optimizer"):
        new_mom = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, mom, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, m: p - learning_rate * m, params, new_mom)
    return new_params, new_mom


def make_train_step(cfg: TransformerConfig, mesh, learning_rate=1e-3):
    """Return (init_fn, step_fn).

    init_fn(key) -> (params, opt_state) placed on the mesh.
    step_fn(state, tokens, targets) -> (state, loss): one fused SGD-momentum
    update. GSPMD mode when cfg.pp == 1, explicit shard_map mode otherwise.
    """
    raw_mesh = getattr(mesh, "mesh", mesh)
    specs = param_specs(cfg)

    def _sharding(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(raw_mesh, s), spec_tree,
            is_leaf=lambda l: isinstance(l, P))

    param_sh = _sharding(specs)

    def init_fn(key):
        params = init_params(key, cfg)
        params = jax.tree_util.tree_map(
            lambda v, sh: jax.device_put(v, sh), params, param_sh)
        momentum = jax.tree_util.tree_map(jnp.zeros_like, params)
        return params, momentum

    if cfg.pp == 1 and cfg.expert_share is not None:
        return init_fn, _profiler.instrument_step(
            _CountedStep(cfg, mesh, param_sh, learning_rate),
            "mx.train_step")
    if cfg.pp == 1:
        def loss_of(params, tokens, targets):
            return loss_fn(params, tokens, targets, cfg, mesh)

        batch_sh = NamedSharding(raw_mesh, P("dp", "sp"))

        @functools.partial(
            jax.jit,  # mxlint: disable=MX022 (benchmark/verification harness: callers AOT-compile the step and account inventories explicitly via comm_model)
            in_shardings=((param_sh, param_sh), batch_sh, batch_sh),
            out_shardings=((param_sh, param_sh), None),
            donate_argnums=(0,))
        def step_fn(state, tokens, targets):
            params, mom = state
            loss, grads = jax.value_and_grad(loss_of)(params, tokens,
                                                      targets)
            new_params, new_mom = _sgd_momentum(params, mom, grads,
                                                learning_rate)
            return (new_params, new_mom), loss
    else:
        from .compat import shard_map
        data_spec = P("dp", "sp")

        def spmd_step(params, mom, tokens, targets):
            def loss_of(ps):
                return _pipeline_loss_local(cfg, ps, tokens, targets)

            loss, grads = jax.value_and_grad(loss_of)(params)
            # grads of replicated params need reduction over dp/sp
            # (shard_map grads are per-device partials on replicated leaves)
            def reduce_grad(g, spec):
                # replicated-axis partial grads must be summed; grads of
                # leaves sharded on an axis are already that shard's grad.
                # 'pp' matters for embed/w_out/ln_f: only one stage touches
                # them, the others contribute zero
                axes = [a for a in ("dp", "sp", "tp", "pp")
                        if not _spec_mentions(spec, a)]
                for a in axes:
                    g = lax.psum(g, a)
                return g

            grads = jax.tree_util.tree_map(
                reduce_grad, grads, specs,
                is_leaf=lambda l: hasattr(l, "shape"))
            new_params, new_mom = _sgd_momentum(params, mom, grads,
                                                learning_rate)
            loss = lax.pmean(lax.pmean(loss, "dp"), "sp")
            return new_params, new_mom, loss

        smapped = shard_map(
            spmd_step, mesh=raw_mesh,
            in_specs=(specs, specs, data_spec, data_spec),
            out_specs=(specs, specs, P()), check_vma=False)

        @jax.jit  # mxlint: disable=MX005,MX022 (one pp-mode train step per make_train_step call, AOT-compiled and inventoried by the bench harness; config and mesh are frozen into the closure, single key)
        def step_fn(state, tokens, targets):
            params, mom = state
            new_params, new_mom, loss = smapped(params, mom, tokens, targets)
            return (new_params, new_mom), loss

    # the jitted step behind the program's own span and counters
    # (profiler.metrics()['train_step']); .lower/.trace reach the jit
    return init_fn, _profiler.instrument_step(step_fn, "mx.train_step")


class _CountedStep:
    """The GSPMD step of a model whose scanned layers are expert shares:
    the same jitted, donated SGD-momentum step, with the shares' counters
    (``expert.MOE_STATS``) carried through it as one more donated array.
    They stay on the device from step to step and are fetched only when
    ``profiler.metrics()['moe']`` is asked for. Callers see ``step(state,
    tokens, targets) -> (state, loss)`` and ``lower`` of the same three."""

    def __init__(self, cfg, mesh, param_sh, learning_rate):
        raw_mesh = getattr(mesh, "mesh", mesh)
        batch_sh = NamedSharding(raw_mesh, P("dp", "sp"))
        everywhere = NamedSharding(raw_mesh, P())
        self.moe_held = cfg.expert_share[1]
        self.moe_counters = None
        self.moe_rows = 0       # of a layer's slot buffer: set by a call
        self._moe_k = cfg.moe_k
        self._everywhere = everywhere

        @functools.partial(
            jax.jit,  # mxlint: disable=MX022 (benchmark/verification harness: callers AOT-compile the step and account inventories explicitly via comm_model)
            in_shardings=((param_sh, param_sh), batch_sh, batch_sh,
                          everywhere),
            out_shardings=((param_sh, param_sh), None, everywhere),
            donate_argnums=(0, 3))
        def step_fn(state, tokens, targets, counters):
            params, mom = state
            (loss, stats), grads = jax.value_and_grad(
                _loss_and_aux, has_aux=True)(params, tokens, targets, cfg,
                                             mesh)
            new_params, new_mom = _sgd_momentum(params, mom, grads,
                                                learning_rate)
            return ((new_params, new_mom), loss,
                    _expert.merge_stats(counters, stats))

        self._jitted = step_fn
        _expert.track(self)

    def _counters(self):
        if self.moe_counters is None:
            self.moe_counters = jax.device_put(
                jnp.zeros(len(_expert.MOE_STATS), jnp.int32),
                self._everywhere)
        return self.moe_counters

    def __call__(self, state, tokens, targets):
        from ..pallas_kernels.grouped_matmul import TILE
        self.moe_rows = _expert.buffer_rows(tokens.size, self._moe_k,
                                            self.moe_held, TILE)
        state, loss, self.moe_counters = self._jitted(
            state, tokens, targets, self._counters())
        return state, loss

    def lower(self, state, tokens, targets):
        return self._jitted.lower(state, tokens, targets, self._counters())

    def trace(self, state, tokens, targets):
        return self._jitted.trace(state, tokens, targets, self._counters())


def _spec_mentions(spec, axis):
    for part in spec:
        if part == axis:
            return True
        if isinstance(part, (tuple, list)) and axis in part:
            return True
    return False
