"""Operations and bytes that the algorithms need, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change how a roofline
share or a utilization is counted.  A roofline share is the least time the chip
could take (the larger of operations over peak FLOP/s and bytes over peak bytes/s)
over the time the kernel took in the device trace.
"""

from __future__ import annotations


def kv_bytes_per_token(n_layers: int, hidden: int, bytes_per_value: int) -> int:
    """Keys and values one context token holds over all layers."""
    return 2 * n_layers * hidden * bytes_per_value


def paged_attention_min_seconds(live_tokens: float, token_steps: float,
                                n_layers: int, hidden: int,
                                bytes_per_value: int,
                                hbm_bytes_per_s: float) -> float:
    """Decode attention is memory-bound: one token-step (all layers) must read
    the keys and values of every live context token once, and computes
    4 * hidden FLOP per byte-pair of them, far under the chip's FLOP/byte
    ratio.  Queries, outputs and block tables are left out (thousands of times
    smaller).  ``live_tokens`` is the mean number of context tokens resident
    over the steps counted, NOT the blocks reserved for them."""
    per_step = live_tokens * kv_bytes_per_token(n_layers, hidden,
                                                bytes_per_value)
    return per_step * token_steps / hbm_bytes_per_s


# ResNet-50 v1 / v1.5 at 224x224: (name, INPUT side, c_in, c_out, kernel, stride,
# count), copied from tools/conv_ceiling.RESNET50_CONVS (bench.py's arithmetic;
# v1.5 moves the stride from a block's first 1x1 to its 3x3, which this table has).
RESNET50_CONVS = [
    ("stem7x7s2",   224,    3,   64, 7, 2, 1),
    # stage 1 @56 (in 64 first block, then 256)
    ("s1_1x1_64_64",    56,  64,   64, 1, 1, 1),
    ("s1_3x3_64",       56,  64,   64, 3, 1, 3),
    ("s1_1x1_64_256",   56,  64,  256, 1, 1, 4),   # 3 expand + 1 downsample
    ("s1_1x1_256_64",   56, 256,   64, 1, 1, 2),
    # stage 2 @28 (3x3 stride-2 entry)
    ("s2_1x1_256_128",  56, 256,  128, 1, 1, 1),
    ("s2_3x3_128_s2",   56, 128,  128, 3, 2, 1),
    ("s2_1x1_256_512s2", 56, 256, 512, 1, 2, 1),   # downsample
    ("s2_1x1_128_512",  28, 128,  512, 1, 1, 4),
    ("s2_1x1_512_128",  28, 512,  128, 1, 1, 3),
    ("s2_3x3_128",      28, 128,  128, 3, 1, 3),
    # stage 3 @14
    ("s3_1x1_512_256",  28, 512,  256, 1, 1, 1),
    ("s3_3x3_256_s2",   28, 256,  256, 3, 2, 1),
    ("s3_1x1_512_1024s2", 28, 512, 1024, 1, 2, 1),
    ("s3_1x1_256_1024", 14, 256, 1024, 1, 1, 6),
    ("s3_1x1_1024_256", 14, 1024, 256, 1, 1, 5),
    ("s3_3x3_256",      14, 256,  256, 3, 1, 5),
    # stage 4 @7
    ("s4_1x1_1024_512", 14, 1024, 512, 1, 1, 1),
    ("s4_3x3_512_s2",   14, 512,  512, 3, 2, 1),
    ("s4_1x1_1024_2048s2", 14, 1024, 2048, 1, 2, 1),
    ("s4_1x1_512_2048",  7, 512, 2048, 1, 1, 3),
    ("s4_1x1_2048_512",  7, 2048, 512, 1, 1, 2),
    ("s4_3x3_512",       7, 512,  512, 3, 1, 2),
]


def conv_flops(batch: int, h_in: int, cin: int, cout: int, k: int,
               stride: int) -> float:
    h_out = -(-h_in // stride)      # SAME padding
    return 2.0 * batch * h_out * h_out * k * k * cin * cout


def resnet50_forward_flops(batch: int, num_classes: int = 1000) -> float:
    """Analytic forward FLOPs (2 * MACs) of ResNet-50 at 224x224: the
    convolutions and the classifier; batch norm, ReLU and pooling are left out."""
    fl = sum(conv_flops(batch, h, cin, cout, k, s) * cnt
             for (_, h, cin, cout, k, s, cnt) in RESNET50_CONVS)
    return fl + 2.0 * batch * 2048 * num_classes


def resnet50_train_flops(batch: int, num_classes: int = 1000) -> float:
    """Forward plus backward (twice the forward: gradients with respect to the
    inputs and to the weights), the usual 3x; nothing recomputed is counted."""
    return 3.0 * resnet50_forward_flops(batch, num_classes)
