"""Layer library (paddle.nn analogue).

Reference surface: python/paddle/nn/ (9.5k LoC of re-exports over fluid
dygraph layers) + python/paddle/fluid/dygraph/nn.py. See SURVEY.md §2.7.
"""

from . import functional, initializer
from .layer import (HookRemoveHelper, Layer, LayerList, Parameter,
                    ParameterList, Sequential, functional_call)
from .layers.common import (GLU, AlphaDropout, Bilinear, CosineSimilarity,
                            Dropout, Dropout2D, ELU, Embedding, Flatten,
                            GELU, Hardshrink, Hardsigmoid, Hardswish,
                            Hardtanh, Identity, LeakyReLU, Linear,
                            LogSigmoid, LogSoftmax, Maxout, Mish, PReLU,
                            Pad2D, ReLU, ReLU6, SELU, CELU, Sigmoid, Silu,
                            Softmax, Softplus, Softshrink, Softsign, Swish,
                            Tanh, Tanhshrink, ThresholdedReLU, Upsample)
from .layers.conv import (AdaptiveAvgPool2D, AdaptiveMaxPool2D, AvgPool2D,
                          AvgPool3D, Conv1D, Conv2D, Conv2DTranspose,
                          Conv3D, Fold, MaxPool2D, MaxPool3D, PixelShuffle,
                          Unfold)
from .layers.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
                          GroupNorm, InstanceNorm1D, InstanceNorm2D,
                          InstanceNorm3D, LayerNorm, LocalResponseNorm,
                          RMSNorm, SpectralNorm, SyncBatchNorm)
from .layers.loss import (BCELoss, BCEWithLogitsLoss, CTCLoss,
                          CosineEmbeddingLoss, CrossEntropyLoss,
                          FusedLinearCrossEntropy, KLDivLoss,
                          L1Loss, MSELoss, MarginRankingLoss, NLLLoss,
                          SmoothL1Loss, TripletMarginLoss)
from .layers.moe import (DroplessMoE, MoELayer,  # noqa: F401
                         moe_param_rule)
from .layers.recompute import recompute_layer  # noqa: F401
from .layers.ssm import Mamba2Mixer  # noqa: F401
from .decode import (BasicDecoder, BeamSearchDecoder,  # noqa: F401
                     DecodeHelper, Decoder, dynamic_decode,
                     GreedyEmbeddingHelper, SampleEmbeddingHelper,
                     TrainingHelper)
from .layers.rnn import (GRU, GRUCell, LSTM, LSTMCell, RNN, RNNCell,  # noqa
                         SimpleRNN,
                         SimpleRNNCell)
from .layers.transformer import (GroupedQueryAttention,
                                 MultiHeadAttention, Transformer,
                                 TransformerDecoder,
                                 TransformerDecoderLayer,
                                 TransformerEncoder,
                                 TransformerEncoderLayer,
                                 rotate_half_rope)
