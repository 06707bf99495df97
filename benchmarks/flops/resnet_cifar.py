"""FLOPs that one optimizer step of the CIFAR ResNet (BasicBlock stages of
64/128/256/512 planes) needs, from shapes. A multiply-add is 2; a convolution
costs 2 * H_out * W_out * k * k * C_in * C_out per image. Backward is twice
the forward. BatchNorm, ReLU, pooling, the codec and the optimizer are left
out."""


def conv_flops(h_out: int, w_out: int, k: int, c_in: int, c_out: int) -> int:
    return 2 * h_out * w_out * k * k * c_in * c_out


def basic_block_flops(h_in: int, c_in: int, planes: int, stride: int) -> int:
    h = h_in // stride
    total = conv_flops(h, h, 3, c_in, planes) + conv_flops(h, h, 3, planes, planes)
    if stride != 1 or c_in != planes:
        total += conv_flops(h, h, 1, c_in, planes)  # the 1x1 shortcut
    return total


def forward_flops_per_image(cfg: dict) -> int:
    size, c_in = cfg["image_size"], cfg["stem_planes"]
    total = conv_flops(size, size, 3, cfg["image_channels"], c_in)
    for stage, (planes, blocks) in enumerate(zip(cfg["stage_planes"], cfg["stage_blocks"])):
        for i in range(blocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            total += basic_block_flops(size, c_in, planes, stride)
            size, c_in = size // stride, planes
    return total + 2 * c_in * cfg["num_classes"]


def train_flops_per_step(cfg: dict, flags: dict) -> int:
    return 3 * int(flags["--batch-size"]) * forward_flops_per_image(cfg)
