"""paddle_tpu.nn.functional — mirrors `python/paddle/nn/functional/`."""
from .activation import *  # noqa: F401,F403
from .common import (  # noqa: F401
    linear, embedding, dropout, dropout2d, dropout3d, alpha_dropout, one_hot,
    label_smooth, interpolate, upsample, unfold, cosine_similarity, bilinear,
    normalize, pixel_shuffle, pad,
)
from .conv import (  # noqa: F401
    conv1d, conv2d, conv3d, conv1d_transpose, conv2d_transpose,
    conv3d_transpose,
)
from .pooling import (  # noqa: F401
    max_pool1d, max_pool2d, max_pool3d, avg_pool1d, avg_pool2d, avg_pool3d,
    max_pool2d_with_index, max_unpool2d,
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_max_pool2d,
)
from .norm import (  # noqa: F401
    batch_norm, layer_norm, rms_norm, instance_norm, group_norm,
    local_response_norm,
)
from .loss import (  # noqa: F401
    cross_entropy, softmax_with_cross_entropy, nll_loss, mse_loss, l1_loss,
    smooth_l1_loss, binary_cross_entropy, binary_cross_entropy_with_logits,
    kl_div, margin_ranking_loss, hinge_embedding_loss, cosine_embedding_loss,
    triplet_margin_loss, square_error_cost, sigmoid_focal_loss, ctc_loss,
    rank_loss, margin_rank_loss, huber_loss, log_loss, bpr_loss, npair_loss,
    center_loss, nce, sampled_softmax_with_cross_entropy, hsigmoid_loss,
    teacher_student_sigmoid_loss, hinge_loss,
)
from .attention import scaled_dot_product_attention  # noqa: F401
from .rotary import rotary_embedding  # noqa: F401
from .short_conv import gated_short_conv, causal_conv_silu  # noqa: F401
from .delta_rule import (  # noqa: F401
    gated_delta_rule, chunked_delta_rule, gated_rms_norm,
)
from .vision import (  # noqa: F401
    affine_grid, grid_sample, temporal_shift, channel_shuffle,
    shuffle_channel, space_to_depth, affine_channel, local_response_norm,
    lrn, deformable_conv,
)
