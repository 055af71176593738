"""Required operations for ResNet-50 (v1.5) from its shapes."""

STAGES = (3, 4, 6, 3)
WIDTH = 64


def forward_macs(cfg):
    """Multiply-adds of one forward pass of one image: every convolution
    (output positions x kernel volume x output channels) and the head."""
    size = cfg["image_size"] // 2                      # 7x7 conv, stride 2
    macs = size * size * WIDTH * 7 * 7 * 3
    size //= 2                                         # 3x3 max pool, stride 2
    cin = WIDTH
    for i, blocks in enumerate(STAGES):
        f = WIDTH * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = size // stride
            macs += size * size * cin * f              # 1x1, before the stride
            macs += out * out * 9 * f * f              # 3x3 carries the stride
            macs += out * out * f * 4 * f              # 1x1
            if cin != 4 * f:
                macs += out * out * cin * 4 * f        # projection shortcut
            cin, size = 4 * f, out
    return macs + cin * cfg["num_classes"]


def train_flops_per_item(cfg, layers, traffic):
    """FLOPs one trained image requires: forward multiply-adds x 2 FLOPs x
    3 (forward, gradient of activations, gradient of weights)."""
    del layers, traffic
    return 6 * forward_macs(cfg)
