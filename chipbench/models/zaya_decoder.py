"""Adapter: one pipeline stage's share of a ``zaya`` decoder (compressed
convolutional attention: q and k mixed by two causal convolutions between
projection and kernel, half of v from the position before, partial rotary
positions; a top-1 expert layer whose router is an MLP with a state carried
from layer to layer; learned residual scaling; a tied head over
``vocab_rows_held``) through ``mxnet_tpu.parallel.transformer.make_train_step``
as a chipbench cell.

From the program it takes ``TransformerConfig``, ``make_train_step``,
``param_specs`` and ``create_mesh``; the weights, the batches (ids drawn from
the rows of the vocabulary held) and the plain reference are the benchmark's
own, made from the seed. ``afmoe_decoder``'s cell does the driving.
"""
from chipbench.models.afmoe_decoder import Cell as _Cell, make_batches
from chipbench.models.mesh_transformer import _key, held, seed_words
from chipbench.reference import zaya_decoder as reference

__all__ = ["build", "make_batches", "seed_words"]


def rope_of(m):
    """(base, dims of a head that rotate) of the layers held, all of one
    kind: the ``rope_parameters`` group of that kind."""
    kind, = set(m["layer_types"])
    rope = m["rope_parameters"][kind]
    return float(rope["rope_theta"]), \
        int(m["head_dim"] * rope["partial_rotary_factor"])


def transformer_config(m, a, seq_len):
    """The program's configuration for the share the file states."""
    from mxnet_tpu.parallel import transformer as T
    assert m["tie_word_embeddings"] and not m["attention_bias"] \
        and not m["lm_head_bias"] and m["sliding_window"] is None \
        and m["hidden_act"] == "silu", "the kind this adapter builds"
    theta, dims = rope_of(m)
    return T.TransformerConfig(
        vocab_size=m["vocab_rows_held"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_size=m["head_dim"],
        max_seq_len=seq_len, dtype=a["dtype"], attn_mode="local",
        remat=a["remat"], loss_chunks=a["loss_chunks"],
        norm_eps=m["rms_norm_eps"],
        num_experts=m["num_experts"], moe_k=m["num_experts_per_tok"],
        moe_hidden=m["moe_intermediate_size"],
        experts_held=(m["first_expert_held"], m["num_experts_held"]),
        route="mlp_softmax", router_hidden=m["router_hidden_size"],
        tied_head=True, qk_mix="cca",
        mix_taps=(m["cca_time0"], m["cca_time1"]), v_shift=True,
        rope_theta=theta, rope_dims=dims, residual_scaling=True)


def weight_shapes(m):
    """({leaf: (shape of one layer, how it is made)} of a layer, of the
    top). How: a fan_in (N(0, 1/fan_in)), None (ones), "zeros", or 0 (the
    router's selection bias, a buffer N(0, 0.01^2))."""
    D, H, G, d = (m["hidden_size"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    F, E, held_e, R = (m["moe_intermediate_size"], m["num_experts"],
                       m["num_experts_held"], m["router_hidden_size"])
    t0, t1 = m["cca_time0"], m["cca_time1"]
    layer = {"ln1": ((D,), None), "wq": ((D, H, d), D), "wk": ((D, G, d), D),
             "wv_cur": ((D, G // 2, d), D),
             "wv_prev": ((D, G - G // 2, d), D),
             "wo": ((H, d, D), H * d), "ln2": ((D,), None),
             "cca_conv0_w": ((t0, (H + G) * d), t0),
             "cca_conv0_b": (((H + G) * d,), t0),
             "cca_conv1_w": ((t1, H + G, d, d), t1 * d),
             "cca_conv1_b": ((H + G, d), t1),
             "cca_temp": ((G,), None),
             "moe_router_down": ((D, R), D), "moe_router_gamma": ((), None),
             "moe_router_norm": ((R,), None), "moe_router_w1": ((R, R), R),
             "moe_router_w2": ((R, R), R), "moe_router_out": ((R, E), R),
             "moe_bias": ((E,), 0),
             "moe_w_gate": ((held_e, D, F), D),
             "moe_w_up": ((held_e, D, F), D),
             "moe_w_down": ((held_e, F, D), F)}
    for half in "12":
        for name, how in zip("stuw", (None, "zeros", None, "zeros")):
            layer["res%s_%s" % (half, name)] = ((D,), how)
    # embedding rows N(0, 1/D), read as they are by the first layer's norm
    # and by the tied head, so that the logits start at unit scale
    top = {"embed": ((m["vocab_rows_held"], D), D), "ln_f": ((D,), None)}
    return layer, top


def make_weights(m, words, dtype):
    """The weights, from the seed alone: N(0, 1/fan_in) matrices (both
    convolutions' taps by their taps x inputs, their biases N(0, 1/taps)),
    scales, the temperature and gamma of one, the residual biases nought,
    the selection bias N(0, 0.01^2). Pure; jit it with the shardings
    wanted."""
    import jax.numpy as jnp
    import jax.random as jr
    layer, top = weight_shapes(m)
    key = _key(words)
    lead = (m["num_hidden_layers"],)

    def leaf(i, shape, how):
        if how is None:
            return jnp.ones(shape, dtype)
        if how == "zeros":
            return jnp.zeros(shape, dtype)
        std = 0.01 if how == 0 else how ** -0.5
        return held(jr.normal(jr.fold_in(key, i), shape, jnp.float32) * std,
                    dtype)

    out = {"layers": {n: leaf(i, lead + layer[n][0], layer[n][1])
                      for i, n in enumerate(sorted(layer))}}
    for i, n in enumerate(top):
        out[n] = leaf(100 + i, *top[n])
    return out


class Cell(_Cell):
    """``afmoe_decoder.Cell`` (the timed entry, the program text, the
    batches on the host, the expert shares' counters) built on this kind's
    configuration and weights, with this kind's leaves and reference."""

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

    def _norms(self, fn, *args):
        import jax
        with self.mesh.mesh:
            sq = jax.device_get(fn(self.state, *args))
        out = {n: float(sq[n]) ** 0.5 for n in reference.TOP}
        out.update({n: float(v) ** 0.5 for n, v in sq["layers"].items()
                    if n not in reference.BUFFERS})
        return out

    def reference(self, steps, variant="exact"):
        import jax
        m = self.m
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype))
        theta, dims = rope_of(m)
        model = reference.Model(
            eps=m["rms_norm_eps"], k=m["num_experts_per_tok"],
            first=m["first_expert_held"], theta=theta, rope_dims=dims)
        return reference.train(
            lambda: weights(self.words), self.batches_on_host(steps),
            self.a["learning_rate"], steps, model, variant=variant,
            devices=list(self.devices))


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
