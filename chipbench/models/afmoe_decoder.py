"""Adapter: one chip's share of an ``afmoe`` decoder (grouped-query heads,
sliding and full layers, a leading dense layer, expert layers that hold
``num_experts_held`` of ``num_experts``, a head over ``vocab_rows_held``)
through ``mxnet_tpu.parallel.transformer.make_train_step`` as a chipbench
cell.

From the program it takes ``TransformerConfig``, ``make_train_step``,
``param_specs`` and ``create_mesh``; the weights, the batches (ids drawn from
the rows of the vocabulary held) and the plain reference are the benchmark's
own, made from the seed. ``mesh_transformer``'s cell does the driving.
"""
from chipbench.models.mesh_transformer import (
    Cell as _Cell, _key, held, seed_words)
from chipbench.reference import afmoe_decoder as reference


def kinds_of(m):
    """"sliding" or "full" for each layer held, in order."""
    return [k.split("_")[0] for k in m["layer_types"]]


def transformer_config(m, a, seq_len):
    """The program's configuration for the share the file states."""
    from mxnet_tpu.parallel import transformer as T
    kinds, dense = kinds_of(m), m["num_dense_layers"]
    period = m["global_attn_every_n_layers"]
    assert kinds[dense:] == kinds[dense:dense + period] * (
        (len(kinds) - dense) // period), "whole periods after the dense layers"
    return T.TransformerConfig(
        vocab_size=m["vocab_rows_held"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_size=m["head_dim"],
        ffn_hidden=m["intermediate_size"], max_seq_len=seq_len,
        dtype=a["dtype"], attn_mode="local", remat=a["remat"],
        loss_chunks=a["loss_chunks"], layer_pattern=tuple(
            kinds[dense:dense + period]), dense_layers=tuple(kinds[:dense]),
        window=m["sliding_window"], rope_on="sliding",
        norm_eps=m["rms_norm_eps"], qk_norm=True, attn_gate=True,
        post_norms=True, embed_scale=m["mup_enabled"],
        num_experts=m["num_experts"], moe_k=m["num_experts_per_tok"],
        moe_hidden=m["moe_intermediate_size"],
        experts_held=(m["first_expert_held"], m["num_experts_held"]),
        moe_shared=m["num_shared_experts"], route_scale=m["route_scale"])


def weight_shapes(m):
    """({leaf: (shape of one layer, fan_in; None a norm's scale, 0 the
    router's bias)} of a dense layer, of an expert layer, of the top)."""
    D, H, G, dh = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    F, Fm, E, held_e = (m["intermediate_size"], m["moe_intermediate_size"],
                        m["num_experts"], m["num_experts_held"])
    Fs, V = Fm * m["num_shared_experts"], m["vocab_rows_held"]
    attn = {"ln1": ((D,), None), "wq": ((D, H, dh), D),
            "wk": ((D, G, dh), D), "wv": ((D, G, dh), D),
            "wo": ((H, dh, D), H * dh), "ln2": ((D,), None),
            "w_attn_gate": ((D, H, dh), D), "q_norm": ((dh,), None),
            "k_norm": ((dh,), None), "ln1_post": ((D,), None),
            "ln2_post": ((D,), None)}
    dense = dict(attn, w_gate=((D, F), D), w_up=((D, F), D),
                 w_down=((F, D), F))
    expert = dict(attn, moe_router=((D, E), D), moe_bias=((E,), 0),
                  moe_w_gate=((held_e, D, Fm), D),
                  moe_w_up=((held_e, D, Fm), D),
                  moe_w_down=((held_e, Fm, D), Fm),
                  ws_gate=((D, Fs), D), ws_up=((D, Fs), D),
                  ws_down=((Fs, D), Fs))
    # embedding rows N(0, 1/D): the program scales them by sqrt(D)
    top = {"embed": ((V, D), D), "ln_f": ((D,), None), "w_out": ((D, V), D)}
    return dense, expert, top


def make_weights(m, words, dtype):
    """The weights, from the seed alone: N(0, 1/fan_in) matrices, scales of
    one, the router's bias N(0, 0.01^2). Pure; jit it with the shardings
    wanted."""
    import jax.numpy as jnp
    import jax.random as jr
    dense, expert, top = weight_shapes(m)
    key = _key(words)
    n_dense = m["num_dense_layers"]
    period = m["global_attn_every_n_layers"]
    periods = (m["num_hidden_layers"] - n_dense) // period

    def leaf(i, shape, fan_in):
        if fan_in is None:
            return jnp.ones(shape, dtype)
        std = 0.01 if fan_in == 0 else fan_in ** -0.5
        return held(jr.normal(jr.fold_in(key, i), shape, jnp.float32) * std,
                    dtype)

    out = {"layers": {n: leaf(i, (periods, period) + expert[n][0],
                              expert[n][1])
                      for i, n in enumerate(sorted(expert))}}
    if n_dense:
        out["dense"] = {n: leaf(50 + i, (n_dense,) + dense[n][0], dense[n][1])
                        for i, n in enumerate(sorted(dense))}
    for i, n in enumerate(top):
        out[n] = leaf(100 + i, *top[n])
    return out


def make_batches(m, t, words):
    """n_batches of ([B, S] tokens, [B, S] next-token targets), ids uniform
    over the rows of the vocabulary held."""
    import jax.numpy as jnp
    import jax.random as jr
    ids = jr.randint(jr.fold_in(_key(words), 7777),
                     (t["n_batches"], t["batch"], t["seq_len"] + 1), 0,
                     m["vocab_rows_held"], jnp.int32)
    return [(ids[b, :, :-1], ids[b, :, 1:]) for b in range(t["n_batches"])]


class Cell(_Cell):
    """``mesh_transformer.Cell`` (the timed entry, the norms' programs, the
    program text) built on this kind's configuration, weights and batches,
    with this kind's leaves, counters and reference."""

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

    def batches_on_host(self, steps):
        import jax
        made = jax.device_get(jax.jit(
            lambda w: make_batches(self.m, self.t, w))(self.words))
        return [made[s % len(made)] for s in range(steps)]

    def _norms(self, fn, *args):
        import jax
        with self.mesh.mesh:
            sq = jax.device_get(fn(self.state, *args))
        out = {n: float(sq[n]) ** 0.5 for n in reference.TOP}
        for group in ("dense", "layers"):
            out.update({reference.leaf_name(group, n): float(v) ** 0.5
                        for n, v in sq.get(group, {}).items()
                        if n not in reference.BUFFERS})
        return out

    def counters(self):
        """The expert shares' counters, as the program keeps them on the
        device; read here, before the first step and after the window."""
        from mxnet_tpu import profiler
        moe = profiler.metrics()["moe"]
        return {n: moe[n] for n in ("layers", "slots_held", "slots_dropped",
                                    "max_load")}

    def reference(self, steps, variant="exact"):
        import jax
        m = self.m
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype))
        model = reference.Model(
            eps=m["rms_norm_eps"], window=m["sliding_window"],
            k=m["num_experts_per_tok"], route_scale=m["route_scale"],
            first=m["first_expert_held"], theta=float(m["rope_theta"]))
        return reference.train(
            lambda: weights(self.words), self.batches_on_host(steps),
            self.a["learning_rate"], steps, kinds_of(m), model,
            variant=variant, devices=list(self.devices))


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
