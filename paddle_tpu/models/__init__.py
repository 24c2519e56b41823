"""Model zoo: the five BASELINE.json configs built with the fluid-style API.

These mirror the reference's book/ test models and benchmark configs
(reference: python/paddle/fluid/tests/book/, BASELINE.md):
MNIST MLP, ResNet-50, BERT, Transformer NMT, DeepFM CTR.
"""
from .mlp import build_mnist_mlp  # noqa: F401
from .resnet import build_resnet  # noqa: F401
from .bert import BertConfig, build_bert_pretrain  # noqa: F401
from .deepfm import build_deepfm  # noqa: F401
from .gpt import (GptConfig, build_gpt_decode,  # noqa: F401
                  build_gpt_generative, build_gpt_prefill)
from .cohere_moe import (CohereMoeConfig,  # noqa: F401
                         build_cohere_moe_generative)
from .seq2seq import (build_seq2seq_infer, build_seq2seq_train,  # noqa: F401
                      build_seq2seq_train_varlen)
