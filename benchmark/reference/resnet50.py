"""Plain reference of the bottleneck ResNet (He et al., arXiv:1512.03385,
Table 1) as the `resnet50` configuration states it: stem 7x7/2 conv, BN,
ReLU, 3x3/2 max pool; stages of [1x1 w, 3x3 w, 1x1 4w] bottlenecks with the
stride on the 3x3 conv and a 1x1 projection shortcut on each stage's first
block; global average pool; dense softmax head; mean cross-entropy. Convs
are bias-free and SAME-padded, BN uses batch statistics (biased variance).

Layer keys are the configuration's vertex names (`stem_conv`, `s0b0_a_bn`,
`out`), so the harness can hand the same weights to the program by name.
Departures from the paper: none in the arithmetic; the weights are random
from the seed (He-normal convs, gamma near 1, beta near 0), not trained.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import plain


def _convs(config):
    """(key, kernel, c_in, c_out, stride, h_in) of every conv, in order."""
    size = config["image_size"]
    out = [("stem_conv", 7, config["channels"], config["stem_width"], 2, size)]
    h = -(-size // 2)      # stem conv, SAME, stride 2
    h = -(-h // 2)         # max pool, SAME, stride 2
    c_in = config["stem_width"]
    for si, (n, w) in enumerate(zip(config["blocks"], config["widths"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"s{si}b{bi}"
            out.append((f"{name}_a_conv", 1, c_in, w, 1, h))
            out.append((f"{name}_b_conv", 3, w, w, stride, h))
            h_out = -(-h // stride)
            out.append((f"{name}_c_conv", 1, w, 4 * w, 1, h_out))
            if bi == 0:
                out.append((f"{name}_sc_conv", 1, c_in, 4 * w, stride, h))
            c_in, h = 4 * w, h_out
    return out


def layers(config):
    """Every conv and dense layer with the sizes its FLOPs follow from."""
    out = [{"kind": "conv", "key": key, "k": k, "c_in": ci, "c_out": co,
            "h_out": -(-h // s), "w_out": -(-h // s)}
           for key, k, ci, co, s, h in _convs(config)]
    out.append({"kind": "dense", "key": "out",
                "n_in": 4 * config["widths"][-1],
                "n_out": config["num_classes"]})
    return out


def init_params(seed, config):
    """All weights from the seed in one jitted call, float32."""
    convs = _convs(config)
    n_feat, n_cls = 4 * config["widths"][-1], config["num_classes"]

    @jax.jit
    def make(key):
        params = {}
        for i, (name, k, ci, co, _, _) in enumerate(convs):
            kw, kg, kb = jax.random.split(jax.random.fold_in(key, i), 3)
            std = (2.0 / (k * k * ci)) ** 0.5
            params[name] = {
                "W": std * jax.random.normal(kw, (k, k, ci, co), jnp.float32)}
            params[name[:-len("conv")] + "bn"] = {
                "gamma": 1.0 + 0.1 * jax.random.normal(kg, (co,), jnp.float32),
                "beta": 0.1 * jax.random.normal(kb, (co,), jnp.float32)}
        kw, kb = jax.random.split(jax.random.fold_in(key, len(convs)))
        params["out"] = {
            "W": (1.0 / n_feat) ** 0.5
            * jax.random.normal(kw, (n_feat, n_cls), jnp.float32),
            "b": 0.01 * jax.random.normal(kb, (n_cls,), jnp.float32)}
        return params

    return make(plain.seed_key(seed))


def loss(params, x, y, config, precision="f32"):
    eps = config["bn_eps"]

    def conv_bn(name, h, k_stride, relu=True):
        z = plain.conv2d(h, params[f"{name}_conv"]["W"], k_stride, "SAME",
                         precision)
        bn = params[f"{name}_bn"]
        z = plain.store(plain.batch_norm_train(z, bn["gamma"], bn["beta"],
                                               eps), precision)
        return jax.nn.relu(z) if relu else z

    def bottleneck(name, h, stride, project):
        c = conv_bn(f"{name}_a", h, 1)
        c = conv_bn(f"{name}_b", c, stride)
        c = conv_bn(f"{name}_c", c, 1, relu=False)
        sc = conv_bn(f"{name}_sc", h, stride, relu=False) if project else h
        return jax.nn.relu(plain.store(c + sc, precision))

    h = conv_bn("stem", plain.store(x.astype(jnp.float32), precision), 2)
    h = plain.max_pool(h, 3, 2, "SAME")
    for si, n in enumerate(config["blocks"]):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            # one block's activations are recomputed in the backward pass,
            # so that the float32 reference fits beside nothing else
            block = jax.checkpoint(
                lambda h, name=f"s{si}b{bi}", stride=stride, project=bi == 0:
                bottleneck(name, h, stride, project))
            h = block(h)
    h = plain.store(jnp.mean(h, axis=(1, 2)), precision)
    logits = plain.dense(h, params["out"]["W"], params["out"]["b"], precision)
    return plain.softmax_cross_entropy(logits, y)
