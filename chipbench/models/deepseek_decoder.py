"""Adapter: one chip's share of a ``deepseek_v3`` decoder (latent attention
over ``heads_held`` of the heads, YaRN positions, a leading dense layer,
expert layers that hold ``num_experts_held`` of ``n_routed_experts`` routed
by group-limited sigmoid scores, a head over ``vocab_rows_held``) through
``mxnet_tpu.parallel.transformer.make_train_step`` as a chipbench cell.

From the program it takes ``TransformerConfig``, ``make_train_step``,
``param_specs`` and ``create_mesh``; the weights, the batches (ids drawn from
the rows of the vocabulary held) and the plain reference are the benchmark's
own, made from the seed. ``afmoe_decoder``'s cell does the driving.
"""
from chipbench.models.afmoe_decoder import Cell as _Cell, make_batches
from chipbench.models.mesh_transformer import _key, held, seed_words
from chipbench.reference import deepseek_decoder as reference

__all__ = ["build", "make_batches", "seed_words"]


def transformer_config(m, a, seq_len):
    """The program's configuration for the share the file states. Where
    the share's heads lie among all (``first_head_held``) changes nothing:
    the weights are the share's own, made from the seed."""
    from mxnet_tpu.parallel import transformer as T
    assert m["scoring_func"] == "sigmoid" and m["norm_topk_prob"] \
        and m["topk_method"] == "noaux_tc" and m["moe_layer_freq"] == 1 \
        and m["hidden_act"] == "silu" and not m["attention_bias"] \
        and not m["tie_word_embeddings"], "the kind this adapter builds"
    if m["v_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"]:
        raise ValueError(
            "v_head_dim %d beside q and k heads of %d + %d: the flash "
            "kernels take one head size" % (
                m["v_head_dim"], m["qk_nope_head_dim"],
                m["qk_rope_head_dim"]))
    dense = m["first_k_dense_replace"]
    return T.TransformerConfig(
        vocab_size=m["vocab_rows_held"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        ffn_hidden=m["intermediate_size"], max_seq_len=seq_len,
        dtype=a["dtype"], attn_mode="local", remat=a["remat"],
        loss_chunks=a["loss_chunks"], dense_layers=("full",) * dense,
        norm_eps=m["rms_norm_eps"],
        num_experts=m["n_routed_experts"], moe_k=m["num_experts_per_tok"],
        moe_hidden=m["moe_intermediate_size"],
        experts_held=(m["first_expert_held"], m["num_experts_held"]),
        moe_shared=m["n_shared_experts"],
        route_scale=m["routed_scaling_factor"],
        route_groups=m["n_group"], route_groups_kept=m["topk_group"],
        attention="mla", q_latent=m["q_lora_rank"],
        kv_latent=m["kv_lora_rank"], nope_head=m["qk_nope_head_dim"],
        rope_head=m["qk_rope_head_dim"], heads_held=m["heads_held"],
        rope_theta=float(m["rope_theta"]),
        rope_scaling=dict(m["rope_scaling"]))


def weight_shapes(m):
    """({leaf: (shape of one layer, fan_in; None a norm's scale, 0 the
    router's bias)} of a dense layer, of an expert layer, of the top)."""
    D, H, h = m["hidden_size"], m["num_attention_heads"], m["heads_held"]
    lq, lkv = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    F, Fm, E, held_e = (m["intermediate_size"], m["moe_intermediate_size"],
                        m["n_routed_experts"], m["num_experts_held"])
    Fs, V = Fm * m["n_shared_experts"], m["vocab_rows_held"]
    attn = {"ln1": ((D,), None), "wq_a": ((D, lq), D),
            "q_a_norm": ((lq,), None), "wq_b": ((lq, h, nope + rope), lq),
            "wkv_a": ((D, lkv + rope), D), "kv_a_norm": ((lkv,), None),
            "wkv_b": ((lkv, h, nope + dv), lkv),
            "wo": ((h, dv, D), H * dv), "ln2": ((D,), None)}
    dense = dict(attn, w_gate=((D, F), D), w_up=((D, F), D),
                 w_down=((F, D), F))
    expert = dict(attn, moe_router=((D, E), D), moe_bias=((E,), 0),
                  moe_w_gate=((held_e, D, Fm), D),
                  moe_w_up=((held_e, D, Fm), D),
                  moe_w_down=((held_e, Fm, D), Fm),
                  ws_gate=((D, Fs), D), ws_up=((D, Fs), D),
                  ws_down=((Fs, D), Fs))
    # embedding rows of unit scale: a hidden state of unit scale going into
    # the first RMSNorm, as the program's own init has them
    top = {"embed": ((V, D), 1), "ln_f": ((D,), None), "w_out": ((D, V), D)}
    return dense, expert, top


def make_weights(m, words, dtype):
    """The weights, from the seed alone: N(0, 1/fan_in) matrices, scales of
    one, the router's bias N(0, 0.01^2). Pure; jit it with the shardings
    wanted."""
    import jax.numpy as jnp
    import jax.random as jr
    dense, expert, top = weight_shapes(m)
    key = _key(words)
    n_dense = m["first_k_dense_replace"]
    n_expert = m["num_hidden_layers"] - n_dense

    def leaf(i, shape, fan_in):
        if fan_in is None:
            return jnp.ones(shape, dtype)
        std = 0.01 if fan_in == 0 else fan_in ** -0.5
        return held(jr.normal(jr.fold_in(key, i), shape, jnp.float32) * std,
                    dtype)

    out = {"layers": {n: leaf(i, (n_expert,) + expert[n][0], expert[n][1])
                      for i, n in enumerate(sorted(expert))}}
    if n_dense:
        out["dense"] = {n: leaf(50 + i, (n_dense,) + dense[n][0], dense[n][1])
                        for i, n in enumerate(sorted(dense))}
    for i, n in enumerate(top):
        out[n] = leaf(100 + i, *top[n])
    return out


def model_of(m):
    """The reference's ``Model`` of the share the file states."""
    y = m["rope_scaling"]
    return reference.Model(
        eps=m["rms_norm_eps"], k=m["num_experts_per_tok"],
        route_scale=m["routed_scaling_factor"], first=m["first_expert_held"],
        groups=m["n_group"], kept=m["topk_group"],
        nope=m["qk_nope_head_dim"], theta=float(m["rope_theta"]),
        yarn=(y["factor"], y["original_max_position_embeddings"],
              y["beta_fast"], y["beta_slow"], y["mscale"],
              y["mscale_all_dim"]))


class Cell(_Cell):
    """``afmoe_decoder.Cell`` (the timed entry, the program text, the
    batches on the host, the norms by leaf) built on this kind's
    configuration and weights, with this kind's counters and reference."""

    def __init__(self, config, traffic, seed, devices):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.parallel import create_mesh
        from mxnet_tpu.parallel import transformer as T
        m, a = config, config["assumed"]
        self.m, self.t, self.a, self.seed = m, traffic, a, seed
        self.devices = devices
        self.dtype = jnp.dtype(a["dtype"])
        cfg = transformer_config(m, a, traffic["seq_len"])
        self.mesh = create_mesh(devices=devices, **traffic.get("mesh",
                                                               {"dp": 1}))
        _, self.step_fn = T.make_train_step(
            cfg, self.mesh, learning_rate=a["learning_rate"])
        raw = self.mesh.mesh
        P = jax.sharding.PartitionSpec
        self.param_sh = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(raw, s), T.param_specs(cfg),
            is_leaf=lambda l: isinstance(l, P))
        batch_sh = jax.sharding.NamedSharding(raw, P("dp", "sp"))
        self.words = seed_words(seed)
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype),
                          out_shardings=self.param_sh)
        with raw:
            params = weights(self.words)
            mom = jax.jit(lambda: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(weights, self.words)),
                out_shardings=self.param_sh)()
            self.batches = jax.jit(
                lambda w: make_batches(m, traffic, w),
                out_shardings=batch_sh)(self.words)
        self.state = (params, mom)

        def sq_norms(tree):
            return jax.tree_util.tree_map(
                lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))), tree)

        self._mom_sq = jax.jit(lambda st: sq_norms(st[1]))
        self._delta_sq = jax.jit(lambda st, w: sq_norms(jax.tree_util.tree_map(
            lambda p, p0: p.astype(jnp.float32) - p0.astype(jnp.float32),
            st[0], make_weights(m, w, self.dtype))))

    def counters(self):
        """The expert shares' counters, as the program keeps them on the
        device, the group-limited routing's among them."""
        from mxnet_tpu import profiler
        moe = profiler.metrics()["moe"]
        return {n: moe[n] for n in ("layers", "slots_held", "slots_dropped",
                                    "max_load", "tokens_to_held_groups")}

    def reference(self, steps, variant="exact"):
        import jax
        m = self.m
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype))
        return reference.train(
            lambda: weights(self.words), self.batches_on_host(steps),
            self.a["learning_rate"], steps, model_of(m), variant=variant,
            devices=list(self.devices))


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
