"""paddle_tpu.nn — mirrors `python/paddle/nn/__init__.py`."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import utils  # noqa: F401

from .layer.layers import Layer, ParamAttr  # noqa: F401
from .layer.container import (  # noqa: F401
    Sequential, LayerList, LayerDict, ParameterList,
)
from .layer.common import (  # noqa: F401
    Identity, Linear, Embedding, Dropout, Dropout2D, Dropout3D, AlphaDropout,
    Flatten, Pad1D, Pad2D, Pad3D, ZeroPad2D, Upsample, UpsamplingNearest2D,
    UpsamplingBilinear2D, Bilinear, CosineSimilarity, PairwiseDistance,
    Unfold, Fold,
)
from .layer.conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, SyncBatchNorm,
    LayerNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm, SpectralNorm, RMSNorm,
)
from .layer.activation import (  # noqa: F401
    ReLU, ReLU6, Sigmoid, Tanh, Silu, Swish, Mish, Softsign, Tanhshrink,
    LogSigmoid, Hardswish, Hardsigmoid, Softplus, ThresholdedReLU, GELU,
    LeakyReLU, ELU, SELU, CELU, Hardtanh, Hardshrink, Softshrink, PReLU,
    RReLU, Maxout, Softmax, LogSoftmax,
)
from .layer.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D, MaxUnPool2D,
)
from .layer.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    SmoothL1Loss, KLDivLoss, MarginRankingLoss, CTCLoss, HingeEmbeddingLoss,
    CosineEmbeddingLoss, TripletMarginLoss,
 HSigmoidLoss,
)
from .layer.rnn import (  # noqa: F401
    RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN, BiRNN, SimpleRNN,
    LSTM, GRU,
)
from .layer.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .clip import (  # noqa: F401
    ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm, clip_grad_norm_,
)
from .layer.vision import PixelShuffle, PixelUnshuffle, ChannelShuffle  # noqa: F401

from ..generation import BeamSearchDecoder  # noqa: F401,E402

from ..generation import dynamic_decode  # noqa: F401,E402
