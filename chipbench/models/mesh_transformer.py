"""Adapter: ``mxnet_tpu.parallel.transformer.make_train_step`` (the mesh
transformer's jitted, donated SGD-momentum step) as a chipbench cell.

From the program it takes ``TransformerConfig``, ``make_train_step`` and
``create_mesh``: the step and the shardings of its state. The weights, the
batches and the plain reference are the benchmark's own, made from the seed.
"""
from chipbench.reference import dense_decoder

TOP = ("embed", "ln_f", "w_out")


def seed_words(seed):
    """The seed as two 31-bit words: an ARGUMENT of the jitted makers, so
    that a new seed finds every program in the compile cache."""
    import numpy as np
    return np.array([seed & 0x7FFFFFFF, seed >> 31], np.uint32)


def _key(words):
    import jax.random as jr
    return jr.fold_in(jr.PRNGKey(words[0]), words[1])


def weight_shapes(m):
    """{leaf: (shape, fan_in or None for a norm's scale)} in a fixed order."""
    L, D, H, F, V = (m["num_hidden_layers"], m["hidden_size"],
                     m["num_attention_heads"], m["intermediate_size"],
                     m["vocab_size"])
    Dh = D // H
    layers = {"ln1": ((L, D), None), "wq": ((L, D, H, Dh), D),
              "wk": ((L, D, H, Dh), D), "wv": ((L, D, H, Dh), D),
              "wo": ((L, H, Dh, D), D), "ln2": ((L, D), None),
              "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
              "w_down": ((L, F, D), F)}
    # the embedding has unit rows, as the program's own init has them: a
    # hidden state of unit scale going into the first RMSNorm
    top = {"embed": ((V, D), 1), "ln_f": ((D,), None), "w_out": ((D, V), D)}
    return layers, top


def held(x, dtype):
    """float32 values rounded to ``dtype``. The explicit reduce_precision
    matters: XLA may drop a convert to bfloat16 and back (excess precision is
    allowed by default), and a first weight regenerated unrounded beside the
    program's rounded one reads as a change the step never made."""
    import jax.numpy as jnp
    from jax import lax
    info = jnp.finfo(dtype)
    return lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def make_weights(m, words, dtype):
    """The weights, from the seed alone: N(0, 1/fan_in) matrices, scales of
    one. Pure; jit it with the shardings wanted."""
    import jax.numpy as jnp
    import jax.random as jr
    layers, top = weight_shapes(m)
    key = _key(words)

    def leaf(i, shape, fan_in):
        if fan_in is None:
            return jnp.ones(shape, dtype)
        return held(jr.normal(jr.fold_in(key, i), shape, jnp.float32)
                    * fan_in ** -0.5, dtype)

    out = {"layers": {n: leaf(i, *layers[n])
                      for i, n in enumerate(layers)}}
    for i, n in enumerate(top):
        out[n] = leaf(100 + i, *top[n])
    return out


def make_batches(m, t, words):
    """n_batches of ([B, S] tokens, [B, S] next-token targets), ids uniform
    over the vocabulary: every row differs."""
    import jax.numpy as jnp
    import jax.random as jr
    ids = jr.randint(jr.fold_in(_key(words), 7777),
                     (t["n_batches"], t["batch"], t["seq_len"] + 1), 0,
                     m["vocab_size"], jnp.int32)
    return [(ids[b, :, :-1], ids[b, :, 1:]) for b in range(t["n_batches"])]


class Cell:
    """One compiled step with its state: set-up builds it, drives it, and
    hands this same object to the window."""

    def __init__(self, config, traffic, seed, devices):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.parallel import create_mesh
        from mxnet_tpu.parallel import transformer as T
        m, a = config, config["assumed"]
        self.m, self.t, self.a, self.seed = m, traffic, a, seed
        self.devices = devices
        self.dtype = jnp.dtype(a["dtype"])
        cfg = T.TransformerConfig(
            vocab_size=m["vocab_size"], dim=m["hidden_size"],
            n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
            ffn_hidden=m["intermediate_size"], max_seq_len=traffic["seq_len"],
            dtype=a["dtype"], attn_mode="local", remat=a["remat"],
            loss_chunks=a["loss_chunks"])
        self.mesh = create_mesh(devices=devices, **traffic.get("mesh",
                                                               {"dp": 1}))
        _, self.step_fn = T.make_train_step(
            cfg, self.mesh, learning_rate=a["learning_rate"])
        raw = self.mesh.mesh
        specs = T.param_specs(cfg)
        P = jax.sharding.PartitionSpec
        self.param_sh = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(raw, s), specs,
            is_leaf=lambda l: isinstance(l, P))
        batch_sh = jax.sharding.NamedSharding(raw, P("dp", "sp"))
        self.words = seed_words(seed)
        weights = jax.jit(lambda w: make_weights(m, w, self.dtype),
                          out_shardings=self.param_sh)
        with raw:
            params = weights(self.words)
            shapes = jax.eval_shape(weights, self.words)
            mom = jax.jit(lambda: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes),
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

    # -- the timed entry ---------------------------------------------------
    def dispatch(self, i):
        """Start step i (0-based) on batch i mod n_batches; returns at once
        with the loss still on the device."""
        tokens, targets = self.batches[i % len(self.batches)]
        with self.mesh.mesh:
            self.state, loss = self.step_fn(self.state, tokens, targets)
        return loss

    @staticmethod
    def read(loss):
        return float(loss)

    # -- what `correct` reads from the timed path -------------------------------
    def _norms(self, fn, *args):
        import jax
        with self.mesh.mesh:
            sq = jax.device_get(fn(self.state, *args))
        out = {n: float(v) ** 0.5 for n, v in sq["layers"].items()}
        out.update({n: float(sq[n]) ** 0.5 for n in TOP})
        return out

    def grad_norms(self):
        """After ONE step the momentum is the gradient the optimizer got."""
        return self._norms(self._mom_sq)

    def delta_norms(self):
        return self._norms(self._delta_sq, self.words)

    def counters(self):
        return None  # the transformer path has no off-path counters yet

    def program_text(self):
        """The compiled step's text: each instruction with the ``mx.*``
        scopes the program gave it (``metadata.op_name``), which the trace's
        events lack. The jitted call has compiled the same program, so this
        is answered from the process's own cache (0.03 s on the chip); the
        harness still asks only in a traced run, after the window."""
        tokens, targets = self.batches[0]
        with self.mesh.mesh:
            return self.step_fn.lower(self.state, tokens,
                                      targets).compile().as_text()

    def free(self):
        import jax
        for leaf in jax.tree_util.tree_leaves((self.state, self.batches)):
            leaf.delete()
        self.state = self.batches = None

    # -- the plain reference ------------------------------------------------------
    def batches_on_host(self, steps):
        import jax
        made = jax.device_get(jax.jit(
            lambda w: make_batches(self.m, self.t, w))(self.words))
        return [made[s % len(made)] for s in range(steps)]

    def reference(self, steps, variant="exact"):
        import jax
        weights = jax.jit(lambda w: make_weights(self.m, w, self.dtype))
        return dense_decoder.train(
            lambda: weights(self.words), self.batches_on_host(steps), self.a["learning_rate"],
            steps, variant=variant, devices=list(self.devices))

    def work(self):
        return {"model": self.m, "batch": self.t["batch"],
                "seq_len": self.t["seq_len"],
                "items_per_step": self.t["batch"] * self.t["seq_len"],
                "item": "tokens", "dtype": self.a["dtype"]}


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
