"""Multiply-accumulates of one image through the CIFAR CNN's forward pass:
convolutions (output positions x window x in x out) and dense layers."""


def forward_macs(image_size=32, classes=10):
    s = image_size
    conv1 = s * s * (5 * 5 * 3) * 64
    conv2 = (s // 2) ** 2 * (5 * 5 * 64) * 64
    dense1 = (s // 4) ** 2 * 64 * 384
    return conv1 + conv2 + dense1 + 384 * 192 + 192 * classes
