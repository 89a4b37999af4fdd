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
import itertools
import math
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
from . import cca as _cca
from . import expert as _expert
from . import mla as _mla
from . import ssm as _ssm
from .expert import moe_ffn

__all__ = ["TransformerConfig", "init_params", "apply", "loss_fn",
           "make_train_step", "param_specs", "ce_local_accum_active",
           "remat_choice"]


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
    # what the layer remat KEEPS beyond a layer's input (jax
    # save_only_these_names). None: the step chooses from its shapes, the
    # state it carries and the device's bytes_limit (``remat_choice``);
    # (): full remat, pinned; a tuple of names: those, pinned. The names
    # there are: "flash_out" and "flash_lse", the flash forward kernel's
    # output and row sums, the residuals its backward kernels read (kept,
    # the backward does not run the forward kernel again); "attn_o", the
    # transposed attention output OUTSIDE the kernel's custom_vjp (it does
    # not reach those residuals: the kernel still runs twice); "ffn_prod",
    # the gated FFN's silu(a) * u (it skips no product: the backward of
    # the gate needs a and u, which are recomputed).
    remat_save: Optional[tuple] = None
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
    # one period of layer kinds: attention "sliding" (a query sees the last
    # ``window`` keys) or "full", or "mamba" (a state-space mixer in
    # attention's place, ``parallel/ssm.py``; its leaves are a stack of
    # their own); the trunk scans over whole periods, the body holding the
    # period's layers in turn. () = every layer "full", scanned one layer a
    # step.
    layer_pattern: tuple = ()
    window: Optional[int] = None
    # attention kinds of the leading dense layers (gated FFN of width
    # ffn_hidden) that come before the scanned periods, or before the
    # scanned layers where there is no layer_pattern. n_layers =
    # len(dense_layers) + periods * len(pattern), or + the scanned layers
    dense_layers: tuple = ()
    rope_on: str = "all"       # 'all' | 'sliding' | 'none': which kinds rotate
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
    # how the share scores its experts: "sigmoid" (expert.route_sigmoid:
    # a selection bias, weights normalised over the k) or "topk_softmax"
    # (the k largest logits, a softmax over those k; no bias leaf)
    route: str = "sigmoid"
    # -- what a hybrid of mixers and attention needs, each defaulting to
    # the program there was ---------------------------------------------------
    residual_mult: float = 1.0         # x + this * (what a layer's half adds)
    embed_mult: float = 1.0            # embedding * this
    logit_mult: float = 1.0            # logits * this
    attn_scale: Optional[float] = None  # scores * this (None: head ** -0.5)
    # the head reads the embedding's rows (no ``w_out`` in the tree); the
    # embedding's gradient is the sum of both uses
    tied_head: bool = False
    # the "mamba" layers' mixer: heads of ssm_head_size with a state of
    # ssm_state each, one group; a causal conv of ssm_conv taps; the scan in
    # chunks of ssm_chunk positions, its heads in blocks chosen from shapes
    # (``ssm.block_heads``)
    ssm_heads: int = 0
    ssm_head_size: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # -- what compressed convolutional attention, a router with a state and
    # learned residual scaling need, each defaulting to the program there
    # was ---------------------------------------------------------------------
    # how q and k are mixed between projection and kernel: "none", or "cca"
    # (``parallel/cca.py``: two causal convolutions over positions of
    # ``mix_taps`` taps, the mean of the latents, unit length times sqrt(d)
    # with a learned temperature on k)
    qk_mix: str = "none"
    mix_taps: tuple = (2, 2)
    # the later half of the key-value heads read v from the position before
    # (``wv_cur`` and ``wv_prev`` in ``wv``'s place)
    v_shift: bool = False
    rope_theta: float = 10000.0
    # dims of a head that rotate, from the first (None: all); the rest pass
    rope_dims: Optional[int] = None
    # x' = (s * x + t) + (u * a + w) for what each half of a layer adds:
    # four learned [dim] vectors a half, in ``residual_mult``'s place
    residual_scaling: bool = False
    # ``route`` "mlp_softmax" (expert.route_mlp_softmax): the router is an
    # MLP of this width whose first state travels from layer to layer
    router_hidden: int = 0
    # -- what latent attention, a share of the heads, YaRN positions and
    # group-limited routing need, each defaulting to the program there was --
    # "qkv": q, k and v projected from the normed input; "mla" (``parallel/
    # mla.py``): through a q latent of ``q_latent`` and a key-value latent of
    # ``kv_latent``, each head's q and k [nope_head | rope_head] (one rope
    # vector of k a position shared by every head), v of the same size
    attention: str = "qkv"
    q_latent: int = 0
    kv_latent: int = 0
    nope_head: int = 0
    rope_head: int = 0
    # "mla" attention as one chip's SHARE of the heads: how many of the
    # n_heads it holds (0 = all); the up-projections, the kernel and the
    # output rows are theirs, the weights handed in already cut to them, and
    # the partial output goes on as it is
    heads_held: int = 0
    # the published ``rope_scaling`` group, or None: {"type" or "rope_type":
    # "yarn", "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "mscale", "mscale_all_dim"} blends the frequencies
    # (``yarn_freqs``) and scales the scores (``scores_scale``)
    rope_scaling: Optional[dict] = None
    # ``route`` "sigmoid" limited to groups: the experts in route_groups
    # groups, the route_groups_kept best by the sum of their two largest
    # biased scores kept, the k chosen inside those (expert.route_sigmoid)
    route_groups: int = 1
    route_groups_kept: int = 1

    def __post_init__(self):
        if self.heads_held and self.attention != "mla":
            raise ValueError(
                "heads_held=%d: a share of the heads is latent attention's "
                "only; attention=%r projects and runs every head"
                % (self.heads_held, self.attention))
        if self.route_groups > 1 and (self.route != "sigmoid"
                                      or self.moe_hidden is None):
            raise ValueError(
                "route_groups=%d: routing limited to groups is the expert "
                "share's sigmoid router only (moe_hidden set, route="
                "'sigmoid'), not route=%r with moe_hidden=%r"
                % (self.route_groups, self.route, self.moe_hidden))

    @property
    def head_dim(self):
        if self.attention == "mla":
            return self.nope_head + self.rope_head
        return self.head_size or self.dim // self.n_heads

    @property
    def heads_here(self):
        """The query heads this chip computes: a share's count, else all."""
        return self.heads_held or self.n_heads

    @property
    def scores_scale(self):
        """What the scores are scaled by where it is not head ** -0.5, else
        None: ``attn_scale``, or YaRN's head ** -0.5 * mscale ** 2 where
        the rope scaling gives an ``mscale_all_dim``."""
        yarn = self.rope_scaling
        if self.attn_scale is not None or not yarn \
                or not yarn.get("mscale_all_dim"):
            return self.attn_scale
        m = yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
        return self.head_dim ** -0.5 * m * m

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
    def router_state(self):
        """Whether the scanned layers hand a router's state on, layer to
        layer: the trunk then carries (x, r) and not x alone."""
        return self.expert_share is not None and self.route == "mlp_softmax"

    @property
    def periods(self):
        """Whole periods of ``layer_pattern`` after the dense layers."""
        n, p = self.n_layers - len(self.dense_layers), len(self.layer_pattern)
        if p == 0 or n <= 0 or n % p:
            raise ValueError(
                "n_layers=%d is not %d dense layers and whole periods of %d"
                % (self.n_layers, len(self.dense_layers), p))
        return n // p

    @property
    def attn_layers(self):
        """Layers with attention: all but the "mamba" ones."""
        if "mamba" not in self.layer_pattern:
            return self.n_layers
        return len(self.dense_layers) + self.periods * sum(
            k != "mamba" for k in self.layer_pattern)


def _rms_norm(x, scale, eps):
    """``eps`` is the configuration's (``TransformerConfig.norm_eps``)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def yarn_mscale(factor, mscale):
    """YaRN's attention factor 0.1 * mscale * ln(factor) + 1 (1 where the
    factor does not stretch)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dims, theta, scaling):
    """YaRN's inverse frequencies [dims / 2] (arXiv:2309.00071, as the
    DeepSeek-V3 family computes them): theta ** (-j / half) for the dims
    that turn fast, the same over ``factor`` for the slow ones, and a
    linear ramp between the correction dims that ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position_embeddings`` give.
    float32 numpy, a constant of the program."""
    import numpy as np
    half = dims // 2
    orig = scaling["original_max_position_embeddings"]

    def dim_of(rotations):
        return dims * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), dims - 1)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / ((high - low) or 0.001), 0, 1)
    extra = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    return (extra / scaling["factor"] * ramp
            + extra * (1 - ramp)).astype(np.float32)


def _rope(x, positions, theta=10000.0, freqs=None, mult=1.0):
    """Rotary position embedding at base ``theta``: dim j of the first half
    rotates with dim j + half. x: [B, H, S, D_h], positions: [S].
    ``freqs``: inverse frequencies [D_h / 2] in the base's place;
    ``mult``: cos and sin times this."""
    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return rot.astype(x.dtype)


def _rotated(cfg, a, positions):
    """``_rope`` at the configuration's base (and its YaRN frequencies and
    factor, where it has them) on a: [B, S, heads, D_h]; where it rotates
    ``rope_dims`` of a head only, the rest pass as they are."""
    yarn, freqs, mult = cfg.rope_scaling, None, 1.0
    if yarn:
        if yarn.get("rope_type", yarn.get("type")) != "yarn":
            raise NotImplementedError("rope_scaling %r" % (yarn,))
        freqs = jnp.asarray(yarn_freqs(cfg.rope_dims or a.shape[-1],
                                       cfg.rope_theta, yarn))
        mult = yarn_mscale(yarn["factor"], yarn.get("mscale", 1)) \
            / yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0))

    def turned(t):
        return jnp.transpose(_rope(jnp.transpose(t, (0, 2, 1, 3)), positions,
                                   cfg.rope_theta, freqs, mult),
                             (0, 2, 1, 3))

    dims = cfg.rope_dims
    if dims is None or dims == a.shape[-1]:
        return turned(a)
    return jnp.concatenate([turned(a[..., :dims]), a[..., dims:]], axis=-1)


def _ffn_kind(cfg, experts=True):
    """The feed-forward of a scanned layer (``experts``) or of a leading
    dense one: "share" (one chip's share of an expert layer), "gshard" (the
    experts over the 'ep' axis) or "gated"."""
    if not experts or cfg.num_experts <= 0:
        return "gated"
    return "gshard" if cfg.moe_hidden is None else "share"


def _layer_leaves(cfg, experts=True, kind="full"):
    """{leaf: (shape of one layer, fan_in or None for a scale that starts
    at one or 0 for the router's bias or "zeros" or a name ``ssm.init_leaf``
    knows, spec of one layer)}: the one table of a layer's leaves, scanned
    (``experts``) or leading dense, of a ``kind``: attention's rows for "full" and "sliding",
    the mixer's for "mamba", the norms' and the feed-forward's for both.
    The plain decoder's nine are the rows that no option adds."""
    D, H, G, Dh = cfg.dim, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if kind == "mamba":
        out = {"ln1": ((D,), None, (None,)), **_ssm.mixer_leaves(cfg),
               "ln2": ((D,), None, (None,))}
    elif cfg.attention == "mla":
        out = {"ln1": ((D,), None, (None,)), **_mla.mla_leaves(cfg),
               "ln2": ((D,), None, (None,))}
    else:
        out = {"ln1": ((D,), None, (None,)),
               "wq": ((D, H, Dh), D, (None, "tp", None)),
               "wk": ((D, G, Dh), D, (None, "tp", None)),
               "wv": ((D, G, Dh), D, (None, "tp", None)),
               "wo": ((H, Dh, D), H * Dh, ("tp", None, None)),
               "ln2": ((D,), None, (None,))}
        if cfg.v_shift:     # this position's heads, then the one before's
            del out["wv"]
            out["wv_cur"] = ((D, G // 2, Dh), D, (None, "tp", None))
            out["wv_prev"] = ((D, G - G // 2, Dh), D, (None, "tp", None))
        if cfg.attn_gate:
            out["w_attn_gate"] = ((D, H, Dh), D, (None, "tp", None))
        if cfg.qk_norm:
            out["q_norm"] = out["k_norm"] = ((Dh,), None, (None,))
        if cfg.qk_mix == "cca":
            out.update(_cca.mix_leaves(cfg))
    if cfg.post_norms:
        out["ln1_post"] = out["ln2_post"] = ((D,), None, (None,))
    if cfg.residual_scaling:
        for half in "12":
            for name, how in zip("stuw", (None, "zeros", None, "zeros")):
                out["res%s_%s" % (half, name)] = ((D,), how, (None,))
    ffn = _ffn_kind(cfg, experts)
    if ffn != "share":
        F = cfg.ffn_hidden
        out.update({"w_gate": ((D, F), D, (None, "tp")),
                    "w_up": ((D, F), D, (None, "tp")),
                    "w_down": ((F, D), F, ("tp", None))})
        if ffn == "gshard":   # beside the gated leaves, which it leaves idle
            E = cfg.num_experts
            out.update({"moe_router": ((D, E), D, (None, None)),
                        "moe_w1": ((E, D, F), D, ("ep", None, "tp")),
                        "moe_w2": ((E, F, D), F, ("ep", "tp", None))})
        return out
    E, Fm, held = cfg.num_experts, cfg.moe_hidden, cfg.expert_share[1]
    if cfg.route == "mlp_softmax":
        R = cfg.router_hidden
        out.update({"moe_router_down": ((D, R), D, (None, None)),
                    "moe_router_gamma": ((), None, ()),
                    "moe_router_norm": ((R,), None, (None,)),
                    "moe_router_w1": ((R, R), R, (None, None)),
                    "moe_router_w2": ((R, R), R, (None, None)),
                    "moe_router_out": ((R, E), R, (None, None))})
    else:
        out["moe_router"] = ((D, E), D, (None, None))
    if cfg.route in ("sigmoid", "mlp_softmax"):
        out["moe_bias"] = ((E,), 0, (None,))
    out.update({"moe_w_gate": ((held, D, Fm), D, (None, None, None)),
                "moe_w_up": ((held, D, Fm), D, (None, None, None)),
                "moe_w_down": ((held, Fm, D), Fm, (None, None, None))})
    if cfg.moe_shared:
        Fs = Fm * cfg.moe_shared
        out.update({"ws_gate": ((D, Fs), D, (None, "tp")),
                    "ws_up": ((D, Fs), D, (None, "tp")),
                    "ws_down": ((Fs, D), Fs, ("tp", None))})
    return out


def _runs(cfg):
    """The period as runs of adjacent layers that share a set of leaves:
    [(the run's stack in the param tree, [the kinds of its layers])], in
    order. The attention kinds share their leaves, a mixer has its own, so
    ("sliding", "sliding", "full") is one run, ``layers``, and ("mamba" x5,
    "full", "mamba" x4) three: ``mamba``, ``layers``, ``mamba_1``. A run is
    a stack and not a slice of one: a slice of stacked weights that feeds a
    scan is a copy, of the weights on the way in and of their gradients on
    the way out. The plain decoder is one run of one "full" layer."""
    seen, out = {}, []
    for leaves, kinds in itertools.groupby(
            cfg.layer_pattern or ("full",),
            lambda kind: "mamba" if kind == "mamba" else "layers"):
        n = seen[leaves] = seen.get(leaves, -1) + 1
        out.append(("%s_%d" % (leaves, n) if n else leaves, list(kinds)))
    return out


def _stacks(cfg):
    """{stack of layers in the param tree: (shape of its leading axes, their
    spec, whether its layers are the scanned ones, the kind whose leaves it
    holds)}. ``layers`` is [L, ...] for the plain decoder ([pp, L/pp, ...]
    in explicit pipeline mode); a pattern model has a stack [periods, n,
    ...] for each run of its period (``_runs``), one row a period and one
    column each of the run's n layers, with its leading dense layers [n,
    ...] beside."""
    if cfg.layer_pattern:
        out = {name: ((cfg.periods, len(kinds)), (None, None), True,
                      "mamba" if kinds[0] == "mamba" else "full")
               for name, kinds in _runs(cfg)}
        if cfg.dense_layers:
            out["dense"] = ((len(cfg.dense_layers),), (None,), False, "full")
        return out
    if cfg.pp > 1:
        assert cfg.n_layers % cfg.pp == 0, "n_layers must divide pp"
        return {"layers": ((cfg.pp, cfg.n_layers // cfg.pp), ("pp", None),
                           True, "full")}
    n = len(cfg.dense_layers)
    out = {"layers": ((cfg.n_layers - n,), (None,), True, "full")}
    if n:   # a plain stack after them: its scan reads each layer's leaves
        #     as they are, where a pattern's slice of them is a copy
        out["dense"] = ((n,), (None,), False, "full")
    return out


def init_params(key, cfg: TransformerConfig):
    """Param pytree. Layer params are STACKED on leading axes (``_stacks``);
    the first is scanned (one compiled layer body) and, for pp, mesh-sharded.
    Every leaf of a layer comes from ``_layer_leaves``."""
    dt = jnp.dtype(cfg.dtype)
    D = cfg.dim

    def norm(k, shape, fan_in):
        return (jr.normal(k, shape) * (fan_in ** -0.5)).astype(dt)

    def keys_of(leaves, salt):
        keys = {n: jr.fold_in(key, salt + i) for i, n in enumerate(leaves)}
        if not cfg.layer_pattern and not cfg.dense_layers:
            # the plain decoder's seeded states were made from split keys:
            # one a matrix by its place, the GShard leaves' from the eighth
            ks = jr.split(key, 8)
            keys.update(zip(
                ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "moe_router", "moe_w1", "moe_w2"),
                [*ks[:7], *jr.split(ks[7], 4)]))
        return keys

    def stack(lead, experts, kind, salt):
        leaves = _layer_leaves(cfg, experts, kind)
        keys = keys_of(leaves, salt)
        out = {}
        for name, (shape, fan_in, _) in leaves.items():
            if fan_in is None:
                out[name] = jnp.ones(lead + shape, dt)
            elif fan_in == "zeros":
                out[name] = jnp.zeros(lead + shape, dt)
            elif isinstance(fan_in, str):
                out[name] = _ssm.init_leaf(keys[name], fan_in,
                                           lead + shape).astype(dt)
            elif fan_in == 0:   # the router's bias: a buffer, N(0, 0.01^2)
                out[name] = (jr.normal(keys[name], lead + shape)
                             * 0.01).astype(dt)
            else:
                out[name] = norm(keys[name], lead + shape, fan_in)
        return out

    emb_key, out_key = jr.split(jr.fold_in(key, 99))
    embed = norm(emb_key, (cfg.vocab_size, D), D)
    scaled = cfg.embed_scale or cfg.embed_mult != 1.0
    params = {
        "embed": embed if scaled else embed * (D ** 0.5),
        "ln_f": jnp.ones((D,), dt),
    }
    if not cfg.tied_head:
        params["w_out"] = norm(out_key, (D, cfg.vocab_size), D)
    for i, (name, (lead, _, experts, kind)) in enumerate(
            _stacks(cfg).items()):
        params[name] = stack(lead, experts, kind, 1000 * (i + 1))
    return params


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs matching init_params structure: the Megatron TP
    layout, column-parallel on heads/ffn over 'tp' (fsdp composes through
    sharding.fsdp's rules, not here)."""
    if cfg.pp > 1:
        # explicit mode indexes embed/w_out with global token ids inside the
        # shard_map body, so they stay replicated across tp
        specs = {"embed": P(None, None), "w_out": P(None, None)}
    else:
        specs = {"embed": P("tp", None), "w_out": P(None, "tp")}
    specs["ln_f"] = P(None)
    if cfg.tied_head:
        del specs["w_out"]
    for name, (_, lead, experts, kind) in _stacks(cfg).items():
        specs[name] = {n: P(*(lead + spec)) for n, (_, _, spec)
                       in _layer_leaves(cfg, experts, kind).items()}
    return specs


# --------------------------------------------------------------------------
# GSPMD mode forward (pp == 1)
# --------------------------------------------------------------------------

def _attention(cfg, mesh, q, k, v, positions, window=None):
    """q: [B, S, H, Dh], k/v: [B, S, G, Dh] -> [B, S, H, Dh]. Global arrays
    (GSPMD mode). ``window``: this layer's queries see that many keys."""
    qt = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, S, Dh]
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if (window is not None or kt.shape[1] != qt.shape[1]
            or cfg.scores_scale is not None) and cfg.attn_mode != "local":
        raise NotImplementedError(
            "grouped key-value heads, windows and a scale of the scores run "
            "in attn_mode='local' only (the flash kernels); attn_mode=%r has "
            "none of them" % cfg.attn_mode)
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
            if cfg.scores_scale is not None:
                attend = functools.partial(attend, scale=cfg.scores_scale)
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
                                     scale=cfg.scores_scale, window=window)
    return jnp.transpose(ot, (0, 2, 1, 3))


def _mesh_sizes(mesh):
    """{axis: size} of a DeviceMesh / jax Mesh; {} for no mesh."""
    if mesh is None:
        return {}
    return {a: int(n) for a, n in
            dict(getattr(mesh, "mesh", mesh).shape).items()}


def _ffn(cfg, lp, h, experts, r=None):
    """The one place that chooses a layer's feed-forward (``_ffn_kind``).
    h: [B, S, D]; ``r``: the router's state from the layer before, where the
    configuration has one (``router_state``). -> (y, the counters of an
    expert share (``MOE_STATS``) or None, the GShard load-balance loss or
    None, this layer's router state, or ``r`` as it came where the layer
    has no router of that kind)."""
    ffn = _ffn_kind(cfg, experts)
    if ffn == "share":
        shared = (lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
            if cfg.moe_shared else None
        share = functools.partial(
            _expert.moe_share, h, bias=lp.get("moe_bias"),
            w_gate=lp["moe_w_gate"], w_up=lp["moe_w_up"],
            w_down=lp["moe_w_down"], shared=shared, k=cfg.moe_k,
            first=cfg.expert_share[0], route_scale=cfg.route_scale,
            route=cfg.route, groups=(cfg.route_groups, cfg.route_groups_kept))
        if cfg.router_state:
            y, stats, r = share({n: lp["moe_router_" + n] for n in
                                 _expert.ROUTER_MLP}, state=r,
                                eps=cfg.norm_eps)
            return y, stats, None, r
        y, stats = share(lp["moe_router"])
        return y, stats, None, r
    if ffn == "gshard":
        y, balance = moe_ffn(h, lp["moe_router"], lp["moe_w1"], lp["moe_w2"],
                             k=cfg.moe_k)
        return y, None, balance, r
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
    prod = _ckpt_name(g * u, "ffn_prod")
    return jnp.einsum("bsf,fd->bsd", prod, lp["w_down"]), None, None, r


def _scaled(a, mult):
    """a * mult, the product in float32 (0.22 held in bfloat16 is 0.2197);
    ``a`` itself where mult is 1."""
    if mult == 1.0:
        return a
    return (a.astype(jnp.float32) * mult).astype(a.dtype)


def _added(cfg, lp, half, x, a):
    """x with what a layer's ``half`` ("1": attention or mixer, "2": the
    feed-forward) adds to it: x + residual_mult * a, or with learned
    scaling (s * x + t) + (u * a + w), taken in float32."""
    if not cfg.residual_scaling:
        return x + _scaled(a, cfg.residual_mult)
    s, t, u, w = (lp["res%s_%s" % (half, n)].astype(jnp.float32)
                  for n in "stuw")
    return ((s * x.astype(jnp.float32) + t)
            + (u * a.astype(jnp.float32) + w)).astype(x.dtype)


def _layer_body(cfg, mesh, positions, x, lp, kind="full", experts=True):
    """One layer. x: [B, S, D], or (x, the router's state of the layer
    before [B, S, router_hidden]) where the configuration has one
    (``router_state``); lp: this layer's params (``_layer_leaves(cfg,
    experts, kind)``); ``kind``: its attention, "full" or "sliding", or
    "mamba" for a mixer in attention's place.
    -> (x or (x, this layer's router state), counters or None, balance
    loss or None) as ``_ffn``."""
    eps, sliding = cfg.norm_eps, kind == "sliding"
    x, r = x if cfg.router_state else (x, None)
    if kind == "mamba":
        with jax.named_scope("mx.ssm_proj"):
            h = _rms_norm(x, lp["ln1"], eps)
        a = _ssm.mixer(h, lp, cfg)
        with jax.named_scope("mx.ssm_proj"):
            x = _added(cfg, lp, "1", x, a)
    else:
        x = _attend(cfg, mesh, positions, x, lp, sliding)
    with jax.named_scope("mx.ffn"):
        y, stats, balance, r = _ffn(cfg, lp, _rms_norm(x, lp["ln2"], eps),
                                    experts, r)
        if cfg.post_norms:
            y = _rms_norm(y, lp["ln2_post"], eps)
        x = _added(cfg, lp, "2", x, y)
        return (x, r) if cfg.router_state else x, stats, balance


def _latent_qkv(cfg, positions, x, lp):
    """q, k, v [B, S, heads held, d] of latent attention (``parallel/
    mla.py``): the input norm under ``mx.attn_proj``, the latents and the
    nope and rope parts laid together under ``mx.mla_latent``, the rotary
    positions of the rope parts under ``mx.attn_proj``."""
    def norm(a, scale):
        return _rms_norm(a, scale, cfg.norm_eps)

    with jax.named_scope("mx.attn_proj"):
        h = norm(x, lp["ln1"])
    with jax.named_scope("mx.mla_latent"):
        q_nope, q_pe, k_nope, k_pe, v = _mla.latents(h, lp, cfg, norm)
    with jax.named_scope("mx.attn_proj"):
        q_pe = _rotated(cfg, q_pe, positions)
        k_pe = _rotated(cfg, k_pe, positions)
    with jax.named_scope("mx.mla_latent"):
        q, k = _mla.joined(q_nope, q_pe, k_nope, k_pe)
    return q, k, v


def _attend(cfg, mesh, positions, x, lp, sliding):
    """x with what the layer's attention adds to it."""
    eps = cfg.norm_eps
    if cfg.attention == "mla":
        q, k, v = _latent_qkv(cfg, positions, x, lp)
        return _attended(cfg, mesh, positions, x, lp, sliding, q, k, v)
    with jax.named_scope("mx.attn_proj"):
        h = _rms_norm(x, lp["ln1"], eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        if cfg.v_shift:
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv_cur"])
            v_prev = jnp.einsum("bsd,dhk->bshk", h, lp["wv_prev"])
        else:
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.attn_gate:
            gate = jnp.einsum("bsd,dhk->bshk", h, lp["w_attn_gate"])
        if cfg.qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
    if cfg.qk_mix != "none" or cfg.v_shift:
        with jax.named_scope("mx.cca_mix"):
            if cfg.qk_mix == "cca":
                q, k = _cca.mix(q, k, lp)
            if cfg.v_shift:
                # the product of the position before: a shift and a product
                # commute, and the product's rows are a sixteenth of h's
                v = jnp.concatenate([v, _cca.shift(v_prev)], axis=2)
    if cfg.rope_on == "all" or (sliding and cfg.rope_on == "sliding"):
        with jax.named_scope("mx.attn_proj"):   # after the mixing stage
            q = _rotated(cfg, q, positions)
            k = _rotated(cfg, k, positions)
    return _attended(cfg, mesh, positions, x, lp, sliding, q, k, v,
                     gate if cfg.attn_gate else None)


def _attended(cfg, mesh, positions, x, lp, sliding, q, k, v, gate=None):
    """x with the attention of q, k and v added: the kernel under
    ``mx.flash``, the gate, output projection and norm under
    ``mx.attn_out``."""
    eps = cfg.norm_eps
    with jax.named_scope("mx.flash"):
        o = _ckpt_name(_attention(cfg, mesh, q, k, v, positions,
                                  cfg.window if sliding else None), "attn_o")
    with jax.named_scope("mx.attn_out"):
        if gate is not None:
            o = o * jax.nn.sigmoid(gate)
        a = jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        if cfg.post_norms:
            a = _rms_norm(a, lp["ln1_post"], eps)
        return _added(cfg, lp, "1", x, a)


def apply(params, tokens, cfg: TransformerConfig, mesh=None,
          return_aux=False):
    """Forward: tokens [B, S] int32 -> logits [B, S, V]. GSPMD mode.
    With return_aux, also returns the summed MoE load-balance loss (0.0
    where no layer has one)."""
    x, _, balance = _hidden(params, tokens, cfg, mesh)
    with jax.named_scope("mx.head_ce"):
        logits = _scaled(jnp.einsum("bsd,dv->bsv", x, _w_out(params, cfg)),
                         cfg.logit_mult)
    if return_aux:
        return logits, 0.0 if balance is None else balance
    return logits


def _w_out(params, cfg):
    """The head's matrix [D, V]: ``w_out``, or the embedding's rows where
    the head is tied to them."""
    return params["embed"].T if cfg.tied_head else params["w_out"]


def _remat_policy(cfg):
    """None = recompute everything (``remat_save`` empty, or None and no
    step to choose for it); else keep the named intermediates
    (save_only_these_names) so the backward skips their producers."""
    if not cfg.remat_save:
        return None
    return jax.checkpoint_policies.save_only_these_names(*cfg.remat_save)


# The share of a device's room (bytes_limit less state, gradients and the
# layers' inputs) that what the layer remat keeps may take. The rest is the
# backward's working set, which grows with the tokens as the kept rows do.
# Set from AOT compiles and the chip's readings (PERF.md section 6, PR 33).
_REMAT_KEEP_SHARE = 1 / 8


def _remat_rows(cfg, batch, seq, sizes):
    """[(names, bytes they hold a layer on one device)]: what the layer
    remat may keep, the recompute saved per byte falling down the table.
    One row: the flash forward kernel's output [b, h, S, Dh] and float32
    row sums [b h, S], on a device's share of batch and heads as
    ``_attention`` cuts them, for each layer that has attention."""
    dp, tp = sizes.get("dp", 1), sizes.get("tp", 1)
    b = batch // dp if batch % dp == 0 else batch
    h = cfg.heads_here // tp if cfg.heads_here % tp == 0 \
        and cfg.kv_heads % tp == 0 else cfg.heads_here
    rows = b * h * seq
    return [(("flash_out", "flash_lse"),
             rows * (cfg.head_dim * jnp.dtype(cfg.dtype).itemsize + 4))]


def remat_choice(cfg, batch, seq, state_bytes, grad_bytes, sizes, limit):
    """What the layer remat of a [batch, seq] step keeps where
    ``cfg.remat_save`` is None -> (names, their bytes over all layers,
    the budget or None). All bytes are one device's: ``state_bytes`` what
    the step carries (weights and momentum), ``grad_bytes`` the gradients,
    ``limit`` the device's ``bytes_limit`` or None where it reports none
    (the CPU, a described topology), which keeps full remat. Rows of
    ``_remat_rows`` are taken in order while they fit the budget."""
    if cfg.remat_save is not None or not cfg.remat or not limit:
        return tuple(cfg.remat_save or ()), 0, None
    dp, sp = sizes.get("dp", 1), sizes.get("sp", 1)
    inputs = cfg.n_layers * (batch * seq // (dp * sp)) * (
        cfg.dim * jnp.dtype(cfg.dtype).itemsize
        + (4 * cfg.router_hidden if cfg.router_state else 0))
    budget = max(0, int(_REMAT_KEEP_SHARE * (
        limit - state_bytes - grad_bytes - inputs)))
    names, kept = (), 0
    for row, nbytes in _remat_rows(cfg, batch, seq, sizes):
        if kept + cfg.attn_layers * nbytes > budget:
            break
        names, kept = names + row, kept + cfg.attn_layers * nbytes
    return names, kept, budget


def _mesh_bytes_limit(mesh):
    """``bytes_limit`` of the first device the mesh names; None where the
    backend reports none (the CPU) or the device is only described."""
    device = getattr(mesh, "mesh", mesh).devices.flat[0]
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def _hidden(params, tokens, cfg, mesh):
    """The trunk up to (but excluding) the output projection: embed, the
    leading dense layers where the tree has them, a scan over the period's
    stacks (``_runs``) whose body runs one period's layers in turn, each
    under the layer remat (a run of attention layers one after the other, a
    run of mixers as an inner scan: one body traced however long the run),
    and the final norm. The plain decoder is the case of one "full" layer a
    period: the scanned slice is that layer's leaves. Where the layers hand
    a router's state on (``router_state``) the carry is (x, r), r nought
    before the first layer; else x alone. -> (x [B, S, D], the expert
    shares' counters summed or None, the GShard layers' balance loss summed
    or None)."""
    with jax.named_scope("mx.embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embed_scale:
            x = (x * (cfg.dim ** 0.5)).astype(x.dtype)
        x = _scaled(x, cfg.embed_mult)
    positions = jnp.arange(tokens.shape[1])
    zero = jnp.zeros(len(_expert.MOE_STATS), jnp.int32) \
        if _ffn_kind(cfg) == "share" else None
    if cfg.router_state:
        x = (x, jnp.zeros(tokens.shape + (cfg.router_hidden,), jnp.float32))

    def layer(kind, experts=True):
        one = functools.partial(_layer_body, cfg, mesh, positions, kind=kind,
                                experts=experts)
        return jax.checkpoint(one, policy=_remat_policy(cfg)) \
            if cfg.remat else one

    at = lambda tree, i: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a[i], tree)

    runs = _runs(cfg)

    def mixers(x, lp):
        x, one, b = layer("mamba")(x, lp)
        return x, (one, b)

    def period(x, lps):
        found = [zero, None]    # the counters and the balance loss so far

        def note(one, b):
            if one is not None:
                found[0] = _expert.merge_stats(found[0], one)
            if b is not None:
                found[1] = b if found[1] is None else found[1] + b

        for name, kinds in runs:
            if kinds[0] == "mamba" and len(kinds) > 1:
                # a run of mixers: one body traced, scanned over its stack
                x, (one, b) = lax.scan(mixers, x, lps[name])
                note(None if one is None else _expert.sum_stats(one),
                     None if b is None else jnp.sum(b))
                continue
            for j, kind in enumerate(kinds):
                x, one, b = layer(kind)(
                    x, at(lps[name], j) if cfg.layer_pattern else lps[name])
                note(one, b)
        return x, tuple(found)

    with jax.named_scope("mx.layer"):
        if "dense" in params:
            for i, kind in enumerate(cfg.dense_layers):
                x, _, _ = layer(kind, False)(x, at(params["dense"], i))
        x, (stats, balance) = lax.scan(
            period, x, {name: params[name] for name, _ in runs})
        if cfg.router_state:
            x, _ = x
    with jax.named_scope("mx.head_ce"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    return (x, None if stats is None else _expert.sum_stats(stats),
            None if balance is None else jnp.sum(balance))


def _chunks(x, targets, n_chunks):
    """x [B, S, D] and targets [B, S] as n_chunks sequence chunks, the
    chunk axis first."""
    B, S, D = x.shape
    C = S // n_chunks
    return (jnp.swapaxes(x.reshape(B, n_chunks, C, D), 0, 1),
            jnp.swapaxes(targets.reshape(B, n_chunks, C), 0, 1))


def _chunk_nll(xi, w_out, ti, logit_mult):
    """One chunk's summed token NLL, from its float32 logits [b, c, V]."""
    logits = _scaled(jnp.einsum("bcd,dv->bcv", xi, w_out,
                                preferred_element_type=jnp.float32),
                     logit_mult)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _chunked_ce(x, w_out, targets, n_chunks, logit_mult=1.0):
    """Mean token NLL with the vocab projection done per sequence chunk.

    lax.map runs chunks sequentially, so peak HBM holds ONE [B, S/n, V]
    f32 tile instead of the full [B, S, V] logits (2+ GB at 32k vocab,
    batch 8, seq 2048). Differentiated, the forward rule
    (``_chunked_ce_fwd``) takes the gradients while each chunk's logits
    are at hand, so the backward recomputes no chunk."""
    B, S, _ = x.shape
    nll = lax.map(lambda a: _chunk_nll(a[0], w_out, a[1], logit_mult),
                  _chunks(x, targets, n_chunks))
    return jnp.sum(nll) / (B * S)


def _chunked_ce_fwd(x, w_out, targets, n_chunks, logit_mult):
    """The loss, and as the residuals its gradients for a cotangent of 1.

    Each chunk's vjp is taken on its logits as they are computed. The
    chunks run last first and dW is summed in w_out's type, as the
    backward of a checkpointed lax.map sums them, so the gradients are the
    ones that backward gives. No logits are kept."""
    B, S, _ = x.shape
    ct = jnp.ones((), jnp.float32) / (B * S)

    def chunk(dw, args):
        xi, ti = args
        nll, vjp = jax.vjp(
            lambda xi, w: _chunk_nll(xi, w, ti, logit_mult), xi, w_out)
        dxi, dwi = vjp(ct)
        return dw + dwi, (nll, dxi)

    dw, (nll, dx) = lax.scan(chunk, jnp.zeros_like(w_out),
                             _chunks(x, targets, n_chunks), reverse=True)
    dx = jnp.swapaxes(dx, 0, 1).reshape(x.shape)
    return jnp.sum(nll) / (B * S), (dx, dw)


def _chunked_ce_bwd(n_chunks, logit_mult, res, ct):
    dx, dw = res
    return (ct * dx).astype(dx.dtype), (ct * dw).astype(dw.dtype), None


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def _chunked_ce_local(x, w_out, targets, n_chunks, mesh, logit_mult=1.0):
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
            logits = _scaled(jnp.einsum("bcd,dv->bcv", xi, wl,
                                        preferred_element_type=jnp.float32),
                             logit_mult)
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
    return _loss_and_stats(params, tokens, targets, cfg, mesh, aux_weight)[0]


def _loss_and_stats(params, tokens, targets, cfg, mesh, aux_weight=0.01):
    """-> (mean token NLL, plus ``aux_weight`` x the GShard layers' balance
    loss where the model has them; the expert shares' counters or None)."""
    if cfg.loss_chunks > 1 and tokens.shape[1] % cfg.loss_chunks != 0:
        # a silent full-logits fallback would re-materialize the
        # [B,S,V] tensor loss_chunks exists to avoid (and OOM)
        raise ValueError(
            "loss_chunks=%d does not divide seq_len=%d; pick a "
            "divisor or set loss_chunks=1"
            % (cfg.loss_chunks, tokens.shape[1]))
    x, stats, balance = _hidden(params, tokens, cfg, mesh)
    with jax.named_scope("mx.head_ce"):
        w_out = _w_out(params, cfg)
        if cfg.loss_chunks <= 1:
            logits = jnp.einsum("bsd,dv->bsv", x, w_out)
            logp = jax.nn.log_softmax(_scaled(logits.astype(jnp.float32),
                                              cfg.logit_mult), axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        elif ce_local_accum_active(cfg, mesh, tokens.shape[0],
                                   tokens.shape[1]):
            loss = _chunked_ce_local(x, w_out, targets, cfg.loss_chunks,
                                     mesh, cfg.logit_mult)
        else:
            loss = _chunked_ce(x, w_out, targets, cfg.loss_chunks,
                               cfg.logit_mult)
    if balance is not None:
        loss = loss + aux_weight * balance  # GShard load-balance pressure
    return loss, stats


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
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(raw_mesh, s), specs,
        is_leaf=lambda l: isinstance(l, P))

    def init_fn(key):
        params = init_params(key, cfg)
        params = jax.tree_util.tree_map(
            lambda v, sh: jax.device_put(v, sh), params, param_sh)
        momentum = jax.tree_util.tree_map(jnp.zeros_like, params)
        return params, momentum

    if cfg.pp == 1:
        step_fn = _Step(cfg, mesh, param_sh, learning_rate)
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


def _with_remat_chosen(cfg, mesh, param_sh, state, shape):
    """``cfg`` with ``remat_save`` settled for a step of ``state`` on
    ``shape`` tokens (``remat_choice``; run while the step is traced, so
    once a batch shape), and the choice noted in
    ``profiler.metrics()['train_step']``."""
    def on_device(tree):
        return sum(math.prod(sh.shard_shape(a.shape)) * a.dtype.itemsize
                   for a, sh in zip(jax.tree_util.tree_leaves(tree),
                                    jax.tree_util.tree_leaves(param_sh)))

    params, mom = state
    weights = on_device(params)     # the gradients are as large again
    names, kept, budget = remat_choice(
        cfg, *shape, weights + on_device(mom), weights, _mesh_sizes(mesh),
        _mesh_bytes_limit(mesh))
    _profiler.note_remat(names, kept, budget)
    return dataclasses.replace(cfg, remat_save=names)


class _Step:
    """The GSPMD train step: the one jitted, donated SGD-momentum step,
    and what a model's step keeps on the device between calls, carried
    through it as trailing donated, replicated arrays: the expert shares'
    counters (``expert.MOE_STATS``) for a model that has them, nothing for
    one that has not. The counters are fetched only when
    ``profiler.metrics()['moe']`` is asked for. Callers see ``step(state,
    tokens, targets) -> (state, loss)`` and ``lower`` / ``trace`` of the
    same three whatever is carried."""

    def __init__(self, cfg, mesh, param_sh, learning_rate):
        raw_mesh = getattr(mesh, "mesh", mesh)
        batch_sh = NamedSharding(raw_mesh, P("dp", "sp"))
        self._everywhere = NamedSharding(raw_mesh, P())
        self._n_carried = 0
        if _ffn_kind(cfg) == "share":
            self._n_carried = 1
            self.moe_held = cfg.expert_share[1]
            self.moe_counters = None
            self.moe_rows = 0   # of a layer's slot buffer: set by a call
            self._moe_k = cfg.moe_k
            _expert.track(self)
        carried_sh = (self._everywhere,) * self._n_carried

        @functools.partial(
            jax.jit,  # mxlint: disable=MX022 (the mesh-native train step: one per make_train_step call, AOT-compiled by its callers, which account its inventories through comm_model)
            in_shardings=((param_sh, param_sh), batch_sh, batch_sh)
            + carried_sh,
            out_shardings=((param_sh, param_sh), None) + carried_sh,
            donate_argnums=(0,) + tuple(range(3, 3 + self._n_carried)))
        def step_fn(state, tokens, targets, *carried):
            params, mom = state
            if "mamba" in cfg.layer_pattern:
                _ssm.note(cfg, *tokens.shape)
            if cfg.qk_mix == "cca":
                _cca.note(cfg, *tokens.shape)
            if cfg.attention == "mla":
                _mla.note(cfg, *tokens.shape)
            (loss, stats), grads = jax.value_and_grad(
                _loss_and_stats, has_aux=True)(
                    params, tokens, targets,
                    _with_remat_chosen(cfg, mesh, param_sh, state,
                                       tokens.shape), mesh)
            new_params, new_mom = _sgd_momentum(params, mom, grads,
                                                learning_rate)
            return ((new_params, new_mom), loss) + tuple(
                _expert.merge_stats(counters, stats) for counters in carried)

        self._jitted = step_fn

    def _carried(self):
        if not self._n_carried:
            return ()
        if self.moe_counters is None:
            self.moe_counters = jax.device_put(
                jnp.zeros(len(_expert.MOE_STATS), jnp.int32),
                self._everywhere)
        return (self.moe_counters,)

    def __call__(self, state, tokens, targets):
        state, loss, *carried = self._jitted(state, tokens, targets,
                                             *self._carried())
        if carried:
            from ..pallas_kernels.grouped_matmul import TILE
            self.moe_counters, = carried
            self.moe_rows = _expert.buffer_rows(tokens.size, self._moe_k,
                                                self.moe_held, TILE)
        return state, loss

    def lower(self, state, tokens, targets):
        return self._jitted.lower(state, tokens, targets, *self._carried())

    def trace(self, state, tokens, targets):
        return self._jitted.trace(state, tokens, targets, *self._carried())


def _spec_mentions(spec, axis):
    for part in spec:
        if part == axis:
            return True
        if isinstance(part, (tuple, list)) and axis in part:
            return True
    return False
