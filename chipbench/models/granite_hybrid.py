"""Adapter: one chip's share of a ``granitemoehybrid`` decoder (Mamba-2
mixers and attention layers in one period, NoPE attention, every layer
followed by ``num_experts_held`` of ``num_local_experts`` routed experts
beside a shared one, a tied head over ``vocab_rows_held``) through
``mxnet_tpu.parallel.transformer.make_train_step`` as a chipbench cell.

From the program it takes ``TransformerConfig``, ``make_train_step``,
``param_specs`` and ``create_mesh``; the weights, the batches (ids drawn from
the rows of the vocabulary held) and the plain reference are the benchmark's
own, made from the seed. ``afmoe_decoder``'s cell does the driving.
"""
from chipbench.models.afmoe_decoder import Cell as _Cell, make_batches
from chipbench.models.mesh_transformer import _key, held, seed_words
from chipbench.reference import granite_hybrid as reference

__all__ = ["build", "make_batches", "seed_words"]


def runs_of(m):
    """The layers held as runs of one kind: [(stack in the program's tree,
    "mamba" or "attention", layers)], read off the program's own table of
    stacks (``mamba``, ``layers``, ``mamba_1`` for mixers, an attention
    layer, mixers): which run is which stack is the program's to say."""
    from mxnet_tpu.parallel import transformer as T
    cfg = transformer_config(m, m["assumed"], m["mamba_chunk_size"])
    return [(stack, "mamba" if kind == "mamba" else "attention", axes[1])
            for stack, (axes, _, scanned, kind) in T._stacks(cfg).items()
            if scanned]


def transformer_config(m, a, seq_len):
    """The program's configuration for the share the file states."""
    from mxnet_tpu.parallel import transformer as T
    assert m["mamba_n_groups"] == 1 and m["mamba_conv_bias"] \
        and not m["mamba_proj_bias"] and not m["attention_bias"] \
        and m["position_embedding_type"] == "nope" \
        and m["tie_word_embeddings"], "the kind this adapter builds"
    assert m["mamba_n_heads"] * m["mamba_d_head"] \
        == m["mamba_expand"] * m["hidden_size"]
    shared, rem = divmod(m["shared_intermediate_size"],
                         m["intermediate_size"])
    assert rem == 0, "the shared expert as whole widths of a routed one"
    return T.TransformerConfig(
        vocab_size=m["vocab_rows_held"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], max_seq_len=seq_len,
        dtype=a["dtype"], attn_mode="local", remat=a["remat"],
        loss_chunks=a["loss_chunks"], layer_pattern=tuple(
            "mamba" if k == "mamba" else "full" for k in m["layer_types"]),
        rope_on="none", norm_eps=m["rms_norm_eps"],
        num_experts=m["num_local_experts"], moe_k=m["num_experts_per_tok"],
        moe_hidden=m["intermediate_size"],
        experts_held=(m["first_expert_held"], m["num_experts_held"]),
        moe_shared=shared, route="topk_softmax",
        residual_mult=m["residual_multiplier"],
        embed_mult=float(m["embedding_multiplier"]),
        logit_mult=1.0 / m["logits_scaling"],
        attn_scale=m["attention_multiplier"], tied_head=True,
        ssm_heads=m["mamba_n_heads"], ssm_head_size=m["mamba_d_head"],
        ssm_state=m["mamba_d_state"], ssm_conv=m["mamba_d_conv"],
        ssm_chunk=m["mamba_chunk_size"])


def weight_shapes(m):
    """({leaf: (shape of one layer, how it is made)} of a mixer layer, of an
    attention layer, of the top). How: a fan_in (N(0, 1/fan_in)), None
    (ones), "a_log" or "dt_bias"."""
    D, H, G = (m["hidden_size"], m["num_attention_heads"],
               m["num_key_value_heads"])
    dh = D // H
    Fm, E, held_e = (m["intermediate_size"], m["num_local_experts"],
                     m["num_experts_held"])
    Fs, V = m["shared_intermediate_size"], m["vocab_rows_held"]
    Hm, N, taps = m["mamba_n_heads"], m["mamba_d_state"], m["mamba_d_conv"]
    inner = Hm * m["mamba_d_head"]
    conv = inner + 2 * m["mamba_n_groups"] * N
    ffn = {"ln2": ((D,), None), "moe_router": ((D, E), D),
           # the published input_linear [E, 2 Fm, D] as its two halves
           "moe_w_gate": ((held_e, D, Fm), D),
           "moe_w_up": ((held_e, D, Fm), D),
           "moe_w_down": ((held_e, Fm, D), Fm),
           "ws_gate": ((D, Fs), D), "ws_up": ((D, Fs), D),
           "ws_down": ((Fs, D), Fs)}
    mixer = dict(ffn, ln1=((D,), None),
                 ssm_in=((D, inner + conv + Hm), D),
                 ssm_conv_w=((taps, conv), taps), ssm_conv_b=((conv,), taps),
                 ssm_dt_bias=((Hm,), "dt_bias"), ssm_a_log=((Hm,), "a_log"),
                 ssm_d=((Hm,), None), ssm_norm=((inner,), None),
                 ssm_out=((inner, D), inner))
    attn = dict(ffn, ln1=((D,), None), wq=((D, H, dh), D),
                wk=((D, G, dh), D), wv=((D, G, dh), D),
                wo=((H, dh, D), H * dh))
    # embedding rows N(0, 1/D): embedding_multiplier scales what is read
    top = {"embed": ((V, D), D), "ln_f": ((D,), None)}
    return mixer, attn, top


def make_weights(m, words, dtype):
    """The weights, from the seed alone: N(0, 1/fan_in) matrices (the conv's
    taps and bias N(0, 1/taps)), scales and D of one, A_log the log of a
    uniform(1, 16), dt_bias the inverse softplus of a log-uniform(0.001,
    0.1) step. Pure; jit it with the shardings wanted."""
    import jax.numpy as jnp
    import jax.random as jr
    mixer, attn, top = weight_shapes(m)
    key = _key(words)
    periods = 1     # one whole period is held: layer_types IS the period

    def leaf(k, shape, how):
        if how is None:
            return jnp.ones(shape, dtype)
        if how == "a_log":
            x = jnp.log(jr.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif how == "dt_bias":
            step = jnp.exp(jr.uniform(k, shape, jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
            x = step + jnp.log(-jnp.expm1(-step))
        else:
            x = jr.normal(k, shape, jnp.float32) * how ** -0.5
        return held(x, dtype)

    out = {}
    for r, (stack, kind, n) in enumerate(runs_of(m)):
        table = mixer if kind == "mamba" else attn
        out[stack] = {name: leaf(jr.fold_in(key, 1000 * r + i),
                                 (periods, n) + table[name][0],
                                 table[name][1])
                      for i, name in enumerate(sorted(table))}
    for i, n in enumerate(top):
        out[n] = leaf(jr.fold_in(key, 100000 + i), *top[n])
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
        """{leaf: norm over all its layers}, the runs of one kind summed."""
        import jax
        with self.mesh.mesh:
            sq = jax.device_get(fn(self.state, *args))
        out = {n: float(sq[n]) for n in reference.TOP}
        for stack, _, _ in runs_of(self.m):
            for n, v in sq[stack].items():
                name = reference.leaf_name(stack, n)
                out[name] = out.get(name, 0.0) + float(v)
        return {n: v ** 0.5 for n, v in out.items()}

    def reference(self, steps, variant="exact"):
        import jax
        m = self.m
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype))
        model = reference.Model(
            eps=m["rms_norm_eps"], k=m["num_experts_per_tok"],
            first=m["first_expert_held"], residual=m["residual_multiplier"],
            embedding=float(m["embedding_multiplier"]),
            logits=1.0 / m["logits_scaling"],
            attention=m["attention_multiplier"], heads=m["mamba_n_heads"],
            head_size=m["mamba_d_head"], state=m["mamba_d_state"],
            chunk=m["mamba_chunk_size"])
        return reference.train(
            lambda: weights(self.words), self.batches_on_host(steps),
            self.a["learning_rate"], steps, runs_of(m), model,
            variant=variant, devices=list(self.devices))


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
