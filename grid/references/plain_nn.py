"""Plain float32 building blocks of the reference models.

Every value is stored in float32.  The products (convolutions, matmuls) name no
precision of their own: they take the one the caller states round them with
``jax.default_matmul_precision`` (check.py: the cell's, from its limits file),
or the platform's default for float32 where none is stated.  No flax, no
kernels, nothing of the program: ``jax.lax`` and ``jax.numpy`` only.
"""

import math

import jax
import jax.numpy as jnp


def conv(x, kernel, stride=1, padding="SAME"):
    """NHWC convolution with an HWIO kernel."""
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))


def dense(x, kernel, bias):
    return jnp.dot(x, kernel) + bias


def group_norm(x, scale, bias, groups, eps=1e-6):
    """Per example, per group of channels: subtract the mean, divide by the
    standard deviation (two passes), then the per-channel scale and bias."""
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return g.reshape(b, h, w, c) * scale + bias


def max_pool_3x3_stride2(x):
    """3x3 window, stride 2, SAME padding (the border is padded with -inf)."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def softmax_cross_entropy_mean(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def init_params(key, shapes):
    """Seeded weights for a ``{path: shape}`` tree: kernels normal with
    variance 1/fan_in, norm scales one, biases zero.  ``shapes`` is a nested
    dict whose leaves are shape tuples, keyed like the model's parameters."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda node: isinstance(node, tuple))
    leaves = []
    for index, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "kernel":
            fan_in = math.prod(shape[:-1])
            leaf = jax.random.normal(jax.random.fold_in(key, index), shape,
                                     jnp.float32) / math.sqrt(fan_in)
        elif name == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "bias":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            raise KeyError("no seeded init for a parameter named %r" % name)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)
