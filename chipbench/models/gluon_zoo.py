"""Adapter: a Gluon model-zoo ResNet v1 trained through
``Trainer.fuse_step`` (the system's own path: eager warm-up step, compile
step, then ONE donated jitted program a step) as a chipbench cell.

From the program it takes the net, the trainer and the fused step. The
weights, the batches and the plain reference are the benchmark's own.
"""
from chipbench.models.mesh_transformer import held, seed_words, _key
from chipbench.reference import resnet_v1


def leaves_of(config):
    return [leaf for _, _, leaves in resnet_v1.segments(
        tuple(config["layers"]), config["classes"]) for leaf in leaves]


def make_weights(config, words, dtype):
    """He-normal convolutions, N(0, 1/fan_in) dense, BatchNorm scales of one
    and shifts of nought, from the seed alone. Pure; jit it."""
    import jax.numpy as jnp
    import jax.random as jr
    import numpy as np
    key = _key(words)
    out = {}
    for i, (name, shape) in enumerate(leaves_of(config)):
        if name.endswith(".gamma"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith((".beta", ".bias")):
            out[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = int(np.prod(shape[1:]))
            gain = 1.0 if name.startswith("dense") else 2.0
            out[name] = held(jr.normal(jr.fold_in(key, i), shape, jnp.float32)
                             * (gain / fan_in) ** 0.5, dtype)
    return out


def make_batches(config, t, words, dtype):
    """n_batches of (images uniform in [0, 1), labels uniform over the
    classes as float32, the way Gluon losses take them)."""
    import jax.numpy as jnp
    import jax.random as jr
    key = jr.fold_in(_key(words), 7777)
    shape = (t["batch"], 3, t["image"], t["image"])
    out = []
    for b in range(t["n_batches"]):
        kx, ky = jr.split(jr.fold_in(key, b))
        out.append((held(jr.uniform(kx, shape, jnp.float32), dtype),
                    jr.randint(ky, (t["batch"],), 0, config["classes"],
                               jnp.int32).astype(jnp.float32)))
    return out


class Cell:
    def __init__(self, config, traffic, seed, devices):
        import jax
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo
        from mxnet_tpu.ndarray import NDArray
        a = config["assumed"]
        self.c, self.t, self.a = config, traffic, a
        self.mx, self.devices = mx, devices
        self.dtype = jnp.dtype(a["dtype"])
        self.words = seed_words(seed)
        self.lr = a["learning_rate"]
        ctx = mx.tpu() if devices[0].platform == "tpu" else mx.cpu()
        net = zoo.ResNetV1(zoo.BottleneckV1, config["layers"],
                           config["channels"], classes=config["classes"])
        net.initialize(ctx=ctx)
        net.hybridize()
        net.cast(a["dtype"])
        made = jax.jit(lambda w: make_batches(config, traffic, w,
                                              self.dtype))(self.words)
        self.batches = [(NDArray(x, ctx=ctx), NDArray(y, ctx=ctx))
                        for x, y in made]
        net(self.batches[0][0])    # finishes deferred init (Gluon's way)
        weights = jax.jit(lambda w: make_weights(config, w, self.dtype))(
            self.words)
        trainable = [p for p in net.collect_params().values()
                     if p.grad_req != "null"]
        names = leaves_of(config)
        if [tuple(p.shape) for p in trainable] != [s for _, s in names]:
            raise RuntimeError("the zoo's parameters are not laid out as the "
                               "reference expects")
        self.params = dict(zip((n for n, _ in names), trainable))
        for n, p in self.params.items():
            p.set_data(NDArray(weights[n], ctx=ctx))
        self.net = net
        self.trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": self.lr, "momentum": a["momentum"]})
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        self.step = self.trainer.fuse_step(lambda x, y: loss(net(x), y))

        def sq(arrays):
            return [jnp.sum(jnp.square(v.astype(jnp.float32)))
                    for v in arrays]

        self._sq = jax.jit(sq)
        self._delta_sq = jax.jit(lambda now, w: sq(
            [v.astype(jnp.float32) - w0.astype(jnp.float32) for v, w0 in
             zip(now, make_weights(config, w, self.dtype).values())]))

    # -- the timed entry ---------------------------------------------------
    def dispatch(self, i):
        x, y = self.batches[i % len(self.batches)]
        return self.step(x, y)

    @staticmethod
    def read(loss):
        """The mean of the per-sample losses the step returns."""
        import numpy as np
        return float(loss.asnumpy().astype(np.float32).mean())

    # -- what `correct` reads from the timed path -------------------------------
    def _named(self, values):
        import jax
        return {n: float(v) ** 0.5
                for n, v in zip(self.params, jax.device_get(values))}

    def grad_norms(self):
        """After ONE step the momentum is -lr times the gradient the
        optimizer got (mom' = 0.9 * 0 - lr * rescaled gradient)."""
        states, idx = self.trainer._updater.states, self.trainer._param2idx
        moms = [states[idx[p.name]]._data for p in self.params.values()]
        return {n: v / self.lr for n, v in
                self._named(self._sq(moms)).items()}

    def delta_norms(self):
        now = [p.data()._data for p in self.params.values()]
        return self._named(self._delta_sq(now, self.words))

    def counters(self):
        c = self.mx.profiler.metrics()["fused_step"]
        return {k: int(c[k]) for k in ("fallbacks", "retraces", "attr_errors",
                                       "health_errors", "mesh_fallbacks")
                if k in c}

    def free(self):
        import gc
        import jax
        self.step = self.trainer = self.net = self.params = None
        self.batches = None
        gc.collect()
        jax.clear_caches()

    # -- the plain reference ------------------------------------------------------
    def reference(self, steps, variant="exact"):
        import jax
        import numpy as np
        made = jax.jit(lambda w: make_batches(self.c, self.t, w,
                                              self.dtype))(self.words)
        batches = [(made[s % len(made)][0],
                    np.asarray(made[s % len(made)][1]).astype(np.int32))
                   for s in range(steps)]
        weights = jax.jit(lambda w: make_weights(self.c, w, self.dtype))
        return resnet_v1.train(
            lambda: weights(self.words), batches, self.lr, steps,
            variant=variant, layers=tuple(self.c["layers"]),
            classes=self.c["classes"])

    def work(self):
        return {"model": self.c, "batch": self.t["batch"],
                "image": self.t["image"],
                "items_per_step": self.t["batch"], "item": "img",
                "dtype": self.a["dtype"]}


def build(config, traffic, seed, devices):
    return Cell(config, traffic, seed, devices)
