"""paddle_tpu.nn — mirrors `python/paddle/nn/`."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer.layers import Layer, ParamAttr  # noqa: F401
from .layer.common import (  # noqa: F401
    Linear, Embedding, Dropout, Dropout2D, Flatten, Identity, Upsample,
    Pad1D, Pad2D, CosineSimilarity, Bilinear, PixelShuffle,
)
from .layer.conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, SyncBatchNorm,
    LayerNorm, RMSNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D,
    InstanceNorm3D, LocalResponseNorm,
)
from .layer.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveMaxPool2D,
)
from .layer.container import Sequential, LayerList, LayerDict, ParameterList  # noqa: F401
from .layer.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    KLDivLoss, SmoothL1Loss, MarginRankingLoss,
)
from .layer.activation import (  # noqa: F401
    ReLU, ReLU6, Sigmoid, Tanh, GELU, Silu, Swish, Mish, LeakyReLU, ELU, SELU,
    Hardtanh, Hardsigmoid, Hardswish, Softplus, Softshrink, Hardshrink,
    Tanhshrink, Softsign, LogSigmoid, Softmax, LogSoftmax, PReLU, Maxout,
    ThresholdedReLU,
)
from .layer.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .layer.rnn import SimpleRNN, LSTM, GRU, RNNCellBase, LSTMCell, GRUCell, SimpleRNNCell, BeamSearchDecoder, dynamic_decode  # noqa: F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
from .control_flow import (  # noqa: F401
    while_loop, fixed_loop, cond, case, switch_case,
    create_array, array_write, array_read, array_length,
)
from .layer.extras import (  # noqa: F401
    RNN, BiRNN, SpectralNorm, Unfold, AlphaDropout,
    UpsamplingBilinear2D, UpsamplingNearest2D, CTCLoss,
    CosineEmbeddingLoss, TripletMarginLoss,
)
