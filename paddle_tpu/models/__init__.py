"""Model zoo (targets from BASELINE.json configs)."""

from .bert import (BertConfig, BertForPretraining, BertModel,
                   bert_base_config, bert_large_config, pretraining_loss)
from .lenet import LeNet
from .resnet import (ResNet, resnet18, resnet34, resnet50, resnet101,
                     resnet152, resnext50_32x4d)
from .mobilenet import (MobileNetV1, MobileNetV2, mobilenet_v1,  # noqa
                        mobilenet_v2)
from .vgg import VGG, vgg11, vgg13, vgg16, vgg19  # noqa: F401
from .transformer_seq2seq import Seq2SeqConfig, TransformerSeq2Seq  # noqa
from .lstm_lm import LMConfig, LSTMLanguageModel  # noqa: F401
from .gpt_lm import GPTConfig, GPTLanguageModel  # noqa: F401
from .word2vec import NGramLM, SkipGramNCE  # noqa: F401
from .recommender import DeepFM, RecommenderSystem  # noqa: F401
from .gan import Discriminator, GANTrainStep, Generator  # noqa: F401
from .crnn_ctc import CRNNCTC  # noqa: F401
from .ssd import SSDLite  # noqa: F401
from .nlp import SentimentBiLSTM, SRLBiLSTMCRF  # noqa: F401
from .transformer_xl import (TransformerXL, TransformerXLConfig,  # noqa
                             TransformerXLTrainStep)
from .ernie import (ErnieConfig, ErnieForPretraining, ErnieModel,  # noqa
                    knowledge_mask)
from .nemotron_h import (CausalLMOutput, NemotronHConfig,  # noqa: F401
                         NemotronHForCausalLM, balance_router_bias,
                         next_token_loss, routing_metrics)
from .sdar_moe import (BlockDiffusionOutput, SdarMoeConfig,  # noqa: F401
                       SdarMoeForCausalLM, block_diffusion_loss,
                       block_diffusion_metrics)
from .kimi_linear import KimiLinearConfig, KimiLinearForCausalLM  # noqa
from .smallthinker import (SmallThinkerConfig,  # noqa: F401
                           SmallThinkerForCausalLM)
