"""Model zoo — mirrors the reference's demo/benchmark/book model families
(SURVEY.md §2.4 v1_api_demo + benchmark/paddle + fluid/tests/book)."""

from .afmoe import AfmoeLM
from .deepseek_v3 import DeepseekV3LM
from .embeddings import DeepFM, Recommender, Word2Vec
from .generative import GAN, VAE
from .lfm2 import Lfm2MoeLM
from .keye_vl2 import KeyeSparseLM
from .mimo_v2 import MimoV2LM
from .image import (AlexNet, GoogleNet, LeNet, ResNet, SmallNet,
                    VGG, resnet50)
from .mlp import MnistMLP
from .nemotron_h import NemotronHLM
from .seq2seq import AttentionSeq2Seq
from .transformer import TransformerBlock, TransformerLM
from .transformer_nmt import CrossAttentionBlock, TransformerSeq2Seq
from .tagger import BiLSTMCRFTagger, LinearCRFTagger
from .text_cls import BiLSTMTextCls, ConvTextCls, LSTMTextCls

__all__ = [
    "AlexNet", "GoogleNet", "MnistMLP", "LeNet", "SmallNet", "VGG", "ResNet", "resnet50",
           "LSTMTextCls", "BiLSTMTextCls", "ConvTextCls",
           "AttentionSeq2Seq", "LinearCRFTagger", "BiLSTMCRFTagger",
           "Word2Vec", "Recommender", "DeepFM", "GAN", "VAE",
           "TransformerLM", "TransformerBlock", "DeepseekV3LM", "Lfm2MoeLM",
           "NemotronHLM", "AfmoeLM", "KeyeSparseLM", "MimoV2LM",
           "TransformerSeq2Seq", "CrossAttentionBlock"]
