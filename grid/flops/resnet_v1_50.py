"""Multiply-accumulates of one image through ResNet-50's forward pass, stride
on the 3x3 convolution (4.1 GMAC at 224x224, 1000 classes)."""

STAGES = (3, 4, 6, 3)


def forward_macs(image_size=224, classes=1000):
    side = -(-image_size // 2)                      # 7x7/2 stem
    macs = side * side * (7 * 7 * 3) * 64
    side = -(-side // 2)                            # 3x3/2 max-pool
    channels = 64
    for stage, count in enumerate(STAGES):
        filters = 64 * 2 ** stage
        for block in range(count):
            stride = 2 if (stage > 0 and block == 0) else 1
            out_side = -(-side // stride)
            macs += side * side * channels * filters                 # 1x1
            macs += out_side * out_side * (3 * 3 * filters) * filters  # 3x3, strided
            macs += out_side * out_side * filters * 4 * filters      # 1x1 x4
            if stride != 1 or channels != 4 * filters:
                macs += out_side * out_side * channels * 4 * filters  # projection
            side, channels = out_side, 4 * filters
    return macs + 2048 * classes
