"""Operations and bytes counted from shapes, for MFU and roofline shares.

A convolution counts 2 * H_out * W_out * k^2 * c_in * c_out operations per
sample and the head 2 * c * classes; biases, ReLU, pooling, the loss and the
optimizer are not counted.  Training counts three forward passes per sample
and step (forward, and the two products of the backward pass).
"""
from __future__ import annotations

LANE, TILE = 128, 256


def cnn_layers(model: dict):
    """(H_out, W_out, k, c_in, c_out) of every convolution, in order."""
    size, chans = model["image_size"], model["channels"]
    convs = [(size, size, 3, 1, chans[0])]
    cin = chans[0]
    for c in chans:
        for _ in range(model["num_blocks"]):
            if cin != c:
                size = -(-size // 2)
            convs += [(size, size, 3, cin, c), (size, size, 3, c, c)]
            if cin != c:
                convs.append((size, size, 1, cin, c))
            cin = c
    return convs


def cnn_forward_flops(model: dict) -> int:
    """Operations of one sample's forward pass."""
    convs = sum(2 * h * w * k * k * ci * co
                for h, w, k, ci, co in cnn_layers(model))
    return convs + 2 * model["channels"][-1] * model["num_classes"]


def cnn_params(model: dict) -> int:
    convs = sum(k * k * ci * co + co for _, _, k, ci, co in cnn_layers(model))
    c = model["channels"][-1]
    return convs + c * model["num_classes"] + model["num_classes"]


def steps_per_event(cfg: dict) -> int:
    """SGD steps of one local round."""
    local = cfg["local"]
    m = cfg["data"]["train_samples"] // cfg["num_clients"]
    b = min(local["batch_size"], m)
    return max(m // b, 1) * local["local_epochs"] * local["local_rounds"]


def event_flops(cfg: dict, traffic: dict) -> int:
    """Model operations per committed event: the local round, the Eq. 1
    accuracy term (VAFL: one forward pass over the test set), and the share
    of the global model's test-set evaluation (one per N events)."""
    f = cnn_forward_flops(cfg["model"])
    b = min(cfg["local"]["batch_size"],
            cfg["data"]["train_samples"] // cfg["num_clients"])
    train = 3 * f * b * steps_per_event(cfg)
    test = f * cfg["data"]["test_samples"]
    value = test if traffic["algorithm"] == "vafl" else 0
    return train + value + test // cfg["num_clients"]


def topk_quant_rows(n: int) -> int:
    """Rows of the padded (M, 128) layout the kernel runs over."""
    chunk = TILE * LANE
    return -(-n // chunk) * chunk // LANE


def topk_quant_bytes(rows: int) -> int:
    """HBM bytes one call moves: the float32 input, the int8 values and
    mask planes, and three 4-byte scalars (threshold, scale, seed)."""
    return rows * LANE * (4 + 1 + 1) + 3 * 4


# per element: |x|, compare, 8 integer hash operations, int->float, scale,
# divide, two clips, add, floor, select
TOPK_QUANT_OPS_PER_ELEMENT = 17


def topk_quant_ops(rows: int) -> int:
    return rows * LANE * TOPK_QUANT_OPS_PER_ELEMENT
