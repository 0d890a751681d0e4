"""Plain reference of ResNet-50 v1 as the program builds it (models/resnet.py):
7x7/2 stem, 3x3/2 max-pool, bottleneck stages (3, 4, 6, 3) of widths 64, 128,
256, 512 (x4 out) with the stride on the 3x3 convolution, a projection
shortcut where the shape changes, 32-group GroupNorm in place of BatchNorm
(the program's stated departure), global average pool, linear head.  Mean
softmax cross-entropy.
"""

import jax
import jax.numpy as jnp
from references.plain_nn import (conv, dense, group_norm, init_params, max_pool_3x3_stride2,
                      softmax_cross_entropy_mean)

STAGES = (3, 4, 6, 3)


def _blocks():
    """(name, filters, stride, has_projection) of every bottleneck, in order."""
    channels = 64
    for stage, count in enumerate(STAGES):
        filters = 64 * 2 ** stage
        for block in range(count):
            stride = 2 if (stage > 0 and block == 0) else 1
            yield ("stage%d_block%d" % (stage + 1, block), channels, filters, stride,
                   stride != 1 or channels != 4 * filters)
            channels = 4 * filters


def param_shapes(image_size=224, classes=1000):
    def norm(c):
        return {"scale": (c,), "bias": (c,)}

    tree = {"stem": {"kernel": (7, 7, 3, 64)}, "stem_norm": norm(64),
            "logits": {"kernel": (2048, classes), "bias": (classes,)}}
    for name, cin, filters, _stride, projection in _blocks():
        block = {
            "conv1": {"kernel": (1, 1, cin, filters)}, "norm1": norm(filters),
            "conv2": {"kernel": (3, 3, filters, filters)}, "norm2": norm(filters),
            "conv3": {"kernel": (1, 1, filters, 4 * filters)}, "norm3": norm(4 * filters),
        }
        if projection:
            block["shortcut"] = {"kernel": (1, 1, cin, 4 * filters)}
            block["shortcut_norm"] = norm(4 * filters)
        tree[name] = block
    return {"params": tree}


def init(key, image_size=224, classes=1000):
    return init_params(key, param_shapes(image_size, classes))


def _gn(x, p):
    return group_norm(x, p["scale"], p["bias"], 32)


def forward(params, images):
    p = params["params"]
    x = conv(images, p["stem"]["kernel"], 2, [(3, 3), (3, 3)])
    x = max_pool_3x3_stride2(jax.nn.relu(_gn(x, p["stem_norm"])))
    for name, _cin, _filters, stride, projection in _blocks():
        b = p[name]
        y = jax.nn.relu(_gn(conv(x, b["conv1"]["kernel"]), b["norm1"]))
        y = jax.nn.relu(_gn(conv(y, b["conv2"]["kernel"], stride), b["norm2"]))
        y = _gn(conv(y, b["conv3"]["kernel"]), b["norm3"])
        if projection:
            x = _gn(conv(x, b["shortcut"]["kernel"], stride), b["shortcut_norm"])
        x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    return dense(x, p["logits"]["kernel"], p["logits"]["bias"])


def loss(params, images, labels):
    return softmax_cross_entropy_mean(forward(params, images), labels)
