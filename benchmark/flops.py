"""Operations a model needs, counted from its shapes.

Everything here counts multiply-accumulates (MACs) from layer shapes and
reports FLOPs as 2 per MAC. A training step is counted as three forward
passes (forward, gradient with respect to the input, gradient with
respect to the weights): the usual "model FLOPs", which leaves out
recomputation, the optimizer and every elementwise operation. Nothing
here is read from the program or from XLA's cost analysis.
"""
from __future__ import annotations


def conv_macs(out_channels, in_channels, kernel, out_hw):
    """One convolution, one image: every output element is a dot product
    over ``in_channels * kernel * kernel`` inputs."""
    return out_channels * in_channels * kernel * kernel * out_hw * out_hw


def dense_macs(out_features, in_features):
    return out_features * in_features


def resnet_v2_forward_macs(units, filter_list, num_classes, image):
    """Forward MACs of one image through the pre-activation bottleneck
    ResNet (He et al. 2016) with the ImageNet stem: 7x7/2 convolution,
    3x3/2 max pooling, then ``len(units)`` stages of bottleneck units
    (1x1, 3x3, 1x1; the first unit of a stage projects its shortcut with
    a 1x1 convolution; stages after the first halve the resolution in
    the 3x3 convolution and the shortcut), global pooling and one dense
    layer. Returns ``(total, per_layer)`` where ``per_layer`` is a list
    of ``(name, macs)``.
    """
    layers = []
    hw = image // 2                      # 7x7 stride 2, pad 3
    layers.append(("conv0", conv_macs(filter_list[0], 3, 7, hw)))
    hw = hw // 2                         # 3x3 max pool stride 2, pad 1
    in_ch = filter_list[0]
    for s, n_units in enumerate(units):
        out_ch = filter_list[s + 1]
        mid = out_ch // 4
        for u in range(n_units):
            stride = 2 if (u == 0 and s > 0) else 1
            name = f"stage{s + 1}_unit{u + 1}"
            layers.append((name + "_conv1", conv_macs(mid, in_ch, 1, hw)))
            hw_out = hw // stride
            layers.append((name + "_conv2", conv_macs(mid, mid, 3, hw_out)))
            layers.append((name + "_conv3",
                           conv_macs(out_ch, mid, 1, hw_out)))
            if u == 0:
                layers.append((name + "_sc",
                               conv_macs(out_ch, in_ch, 1, hw_out)))
            hw, in_ch = hw_out, out_ch
    layers.append(("fc1", dense_macs(num_classes, in_ch)))
    return sum(m for _, m in layers), layers


def lstm_lm_forward_macs(vocab, embed, hidden, layers):
    """Forward MACs of one token through embedding (a lookup: none),
    ``layers`` LSTM layers (four gates, each a product with the input
    and with the previous hidden state) and the output projection.
    Returns ``(total, per_layer)``."""
    out = []
    in_size = embed
    for i in range(layers):
        out.append((f"lstm{i}", 4 * hidden * (in_size + hidden)))
        in_size = hidden
    out.append(("decoder", dense_macs(vocab, hidden)))
    return sum(m for _, m in out), out


def train_flops(forward_macs):
    """FLOPs of one trained item: 2 per MAC, three passes."""
    return 2 * 3 * forward_macs


def forward_flops(forward_macs):
    return 2 * forward_macs
