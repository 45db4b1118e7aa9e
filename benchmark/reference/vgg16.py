"""Plain reference of VGG configuration D (Simonyan & Zisserman,
arXiv:1409.1556, Table 1) as the `vgg16` configuration states it: blocks of
3x3 SAME convs with bias and ReLU, a 2x2/2 max pool after each block,
flatten in (row, column, channel) order, two ReLU dense layers and a dense
softmax head; mean cross-entropy.

Layer keys are the positions of the layers in the program's sequential
configuration ("0", "1", "3", ... — the pools hold no weights), so the
harness can hand the same weights to the program by position. Weights are
random from the seed, not trained: Glorot-uniform, the initializer the
source's Keras configuration names (He-normal weights diverge within three
steps of plain SGD at the configuration's learning rate of 0.1), and small
random biases where Keras has zeros, so that no two leaves are alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import plain


def _plan(config):
    """[("conv", key, c_in, c_out, h) | ("pool",) | ("dense", key, n_in,
    n_out)] in the order of the sequential configuration."""
    out, idx = [], 0
    c_in, h = config["channels"], config["image_size"]
    for width, n in config["conv_blocks"]:
        for _ in range(n):
            out.append(("conv", str(idx), c_in, width, h))
            c_in, idx = width, idx + 1
        out.append(("pool",))
        idx, h = idx + 1, h // 2
    n_in = h * h * c_in
    for n_out in list(config["dense_widths"]) + [config["num_classes"]]:
        out.append(("dense", str(idx), n_in, n_out))
        n_in, idx = n_out, idx + 1
    return out


def layers(config):
    out = []
    for item in _plan(config):
        if item[0] == "conv":
            _, key, ci, co, h = item
            out.append({"kind": "conv", "key": key, "k": 3, "c_in": ci,
                        "c_out": co, "h_out": h, "w_out": h})
        elif item[0] == "dense":
            _, key, n_in, n_out = item
            out.append({"kind": "dense", "key": key, "n_in": n_in,
                        "n_out": n_out})
    return out


def init_params(seed, config):
    """All weights from the seed in one jitted call, float32."""
    plan = [p for p in _plan(config) if p[0] != "pool"]

    @jax.jit
    def make(key):
        params = {}
        for i, item in enumerate(plan):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            if item[0] == "conv":
                _, name, ci, co, _ = item
                shape, fans = (3, 3, ci, co), 9 * ci + 9 * co
            else:
                _, name, n_in, co = item
                shape, fans = (n_in, co), n_in + co
            limit = (6.0 / fans) ** 0.5
            params[name] = {
                "W": jax.random.uniform(kw, shape, jnp.float32, -limit,
                                        limit),
                "b": 0.01 * jax.random.normal(kb, (co,), jnp.float32)}
        return params

    return make(plain.seed_key(seed))


def loss(params, x, y, config, precision="f32"):
    plan = _plan(config)
    last = [p for p in plan if p[0] == "dense"][-1][1]

    def run(items, h):
        for item in items:
            if item[0] == "conv":
                p = params[item[1]]
                h = jax.nn.relu(plain.conv2d(h, p["W"], 1, "SAME", precision,
                                             p["b"]))
            elif item[0] == "pool":
                h = plain.max_pool(h, 2, 2, "VALID")
            else:
                if h.ndim == 4:
                    h = h.reshape(h.shape[0], -1)
                p = params[item[1]]
                h = plain.dense(h, p["W"], p["b"], precision)
                if item[1] != last:
                    h = jax.nn.relu(h)
        return h

    # one conv block at a time is recomputed in the backward pass, so that
    # the float32 reference fits beside nothing else
    h, block = plain.store(x.astype(jnp.float32), precision), []
    for item in plan:
        block.append(item)
        if item[0] == "pool":
            h = jax.checkpoint(lambda h, items=tuple(block): run(items, h))(h)
            block = []
    logits = run(block, h)
    return plain.softmax_cross_entropy(logits, y)
