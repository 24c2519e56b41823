"""Model builders in the fluid-style API.

Training and inference programs that mirror the reference's book/ test
models and benchmark configs (reference: python/paddle/fluid/tests/book/,
BASELINE.md): MNIST MLP, ResNet-50, BERT, Transformer NMT, DeepFM CTR. And
eight decoders for ``serving.GenerativeEngine``: ``gpt``, ``cohere_moe``,
``qwen3_next``, ``glm4_moe_lite``, ``sdar_moe``, ``granite_moe_hybrid``,
``mimo_v2_flash``, ``xing4``, each its configuration, its block, its state
table and its cache handles over ``decoder`` (what they share, once; none
imports another). The names below are the package's exports; the later
decoders are imported from their modules.
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
