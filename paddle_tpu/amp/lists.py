"""AMP op lists — which ops run in low precision.

Analog of python/paddle/fluid/contrib/mixed_precision/fp16_lists.py
(AutoMixedPrecisionLists) and dygraph amp lists. On TPU the low-precision
dtype is bfloat16: matmuls/convs go to the MXU in bf16; numerically
sensitive reductions/normalizations stay in float32.
"""

# Ops that benefit from bf16 (MXU-bound) — the white list.
WHITE_LIST = {
    "conv2d", "depthwise_conv2d", "conv3d", "conv2d_transpose",
    "matmul", "matmul_v2", "mul", "fused_attention_qkv",
}

# Numerically dangerous in low precision — forced float32.
BLACK_LIST = {
    "exp", "square", "log", "mean", "sum", "cos_sim",
    "softmax", "log_softmax", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "cross_entropy",
    "layer_norm", "batch_norm", "group_norm", "instance_norm",
    "reduce_sum", "reduce_mean", "reduce_prod",
    "squared_l2_norm", "p_norm", "norm", "logsumexp",
    # rotary tables and router scores stay float32 (a bf16 angle or score
    # moves positions and expert choices); rms_norm upcasts inside
    "rotary_embedding", "moe_router",
}

# Everything else runs in whatever dtype its inputs already have.


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(WHITE_LIST)
        self.black_list = set(BLACK_LIST)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
            self.black_list -= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
            self.white_list -= set(custom_black_list)
