"""paddle_tpu.nn.functional — the functional op surface.

Analog of ``python/paddle/nn/functional/`` (reference). All ops are XLA-
lowerable framework primitives; attention routes to Pallas on TPU.
"""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import (  # noqa: F401
    causal_depthwise_conv1d, conv1d, conv2d, conv3d, conv1d_transpose,
    conv2d_transpose, conv3d_transpose,
)
from .pooling import (  # noqa: F401
    max_unpool1d, max_unpool2d, max_unpool3d,
    fractional_max_pool2d, fractional_max_pool3d,
    max_pool1d, max_pool2d, max_pool3d, avg_pool1d, avg_pool2d, avg_pool3d,
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool1d, adaptive_max_pool2d, adaptive_max_pool3d,
)
from .norm import (  # noqa: F401
    layer_norm, rms_norm, batch_norm, instance_norm, group_norm,
    local_response_norm, spectral_norm, fused_residual_norm,
)
from .loss import (  # noqa: F401
    cross_entropy, softmax_with_cross_entropy, nll_loss, mse_loss, l1_loss,
    smooth_l1_loss, binary_cross_entropy, binary_cross_entropy_with_logits,
    kl_div, margin_ranking_loss, hinge_embedding_loss, cosine_embedding_loss,
    triplet_margin_loss, square_error_cost, sigmoid_focal_loss, log_loss,
    ctc_loss, huber_loss, hsigmoid_loss, rnnt_loss,
    margin_cross_entropy, class_center_sample,
)
from .attention import (  # noqa: F401
    scaled_dot_product_attention, flash_attention, flash_attn_qkvpacked,
    flash_attn_unpadded, kda_chunk, sdp_kernel,
)
from .ring_attention import ring_flash_attention  # noqa: F401
from .vision_ops import (  # noqa: F401
    grid_sample, affine_grid, fold, channel_shuffle, temporal_shift,
    sequence_mask, logit, pairwise_distance, soft_margin_loss,
    multi_label_soft_margin_loss, gaussian_nll_loss, poisson_nll_loss,
)
