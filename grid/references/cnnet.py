"""Plain reference of the CIFAR CNN (reference experiments/cnnet.py, as the
program's models/cnnet.py states it): two 5x5x64 convolutions, each with a
ReLU, a 3x3/2 max-pool and an 8-group GroupNorm (pool before the norm in the
first stage, after it in the second), then dense 384, dense 192 and a linear
head.  Mean softmax cross-entropy.
"""

from references.plain_nn import (conv, dense, group_norm, init_params, max_pool_3x3_stride2,
                      softmax_cross_entropy_mean)
import jax


def param_shapes(image_size=32, classes=10):
    flat = (image_size // 4) ** 2 * 64
    return {"params": {
        "conv1": {"kernel": (5, 5, 3, 64), "bias": (64,)},
        "norm1": {"scale": (64,), "bias": (64,)},
        "conv2": {"kernel": (5, 5, 64, 64), "bias": (64,)},
        "norm2": {"scale": (64,), "bias": (64,)},
        "dense1": {"kernel": (flat, 384), "bias": (384,)},
        "dense2": {"kernel": (384, 192), "bias": (192,)},
        "logits": {"kernel": (192, classes), "bias": (classes,)},
    }}


def init(key, image_size=32, classes=10):
    return init_params(key, param_shapes(image_size, classes))


def forward(params, images):
    p = params["params"]
    x = conv(images, p["conv1"]["kernel"]) + p["conv1"]["bias"]
    x = max_pool_3x3_stride2(jax.nn.relu(x))
    x = group_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], 8)
    x = conv(x, p["conv2"]["kernel"]) + p["conv2"]["bias"]
    x = group_norm(jax.nn.relu(x), p["norm2"]["scale"], p["norm2"]["bias"], 8)
    x = max_pool_3x3_stride2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(x, p["dense1"]["kernel"], p["dense1"]["bias"]))
    x = jax.nn.relu(dense(x, p["dense2"]["kernel"], p["dense2"]["bias"]))
    return dense(x, p["logits"]["kernel"], p["logits"]["bias"])


def loss(params, images, labels):
    return softmax_cross_entropy_mean(forward(params, images), labels)
