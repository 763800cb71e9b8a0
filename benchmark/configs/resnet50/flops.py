"""Operations a training step of the ResNet needs, from its shapes: 2 flops
a multiply-accumulate of every convolution and of the classifier, forward;
backward twice that (data and weight gradients), the first convolution's data
gradient included as bench.py's 3 x 2 x 4.089e9 has it. Batch normalisation,
activations and pooling count nothing. Checked against XLA's cost analysis in
tests/bench_yardstick/test_flops.py.
"""


def forward_macs_per_image(cfg):
    units, filters = cfg["units"], cfg["filter_list"]
    h = cfg["image_shape"][1] // 2            # conv0, stride 2
    macs = filters[0] * 3 * 49 * h * h
    h //= 2                                   # max pool
    cin = filters[0]
    for i, n in enumerate(units):
        cout = filters[i + 1]
        mid = cout // 4
        for j in range(n):
            stride = 2 if (j == 0 and i > 0) else 1
            ho = h // stride
            macs += mid * cin * h * h             # conv1 1x1 at the input size
            macs += mid * mid * 9 * ho * ho       # conv2 3x3, strided
            macs += cout * mid * ho * ho          # conv3 1x1
            if j == 0:
                macs += cout * cin * ho * ho      # projection shortcut
            cin, h = cout, ho
    return macs + cfg["num_classes"] * cin


def train_flops_per_item(cfg, traffic):
    return 3 * 2 * forward_macs_per_image(cfg)
