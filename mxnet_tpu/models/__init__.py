"""mxnet_tpu.models — in-tree model families.

Parity: python/mxnet/gluon/model_zoo (vision) plus the GluonNLP-era
transformer models the BASELINE configs require (BERT, GPT-2, Sockeye-style
transformer) — all built on TP/SP-aware blocks (see models.transformer).
"""
from . import vision
from .bert import BERTForPretrain, BERTModel, get_bert
from .deepseek_v3 import DeepseekV3Model, get_deepseek_v3
from .gpt2 import GPT2Model, get_gpt2, gpt2_lm_loss
from .granite_hybrid import GraniteHybridModel, get_granite_hybrid
from .mellum import MellumModel, get_mellum
from .moe import MoELayer, MoETransformerBlock, pop_aux_losses
from .nemotron_h import NemotronHModel, get_nemotron_h
from .ouro import OuroModel, get_ouro
from .phi4_flash import Phi4FlashModel, get_phi4_flash
from .qwen3_next import Qwen3NextModel, get_qwen3_next
from .nmt import TransformerDecoderBlock, TransformerNMT, get_nmt, nmt_loss
from .stacked import StackedGPT2Model, get_stacked_gpt2
from .transformer import (MultiHeadAttention, PositionwiseFFN,
                          TransformerBlock, TransformerEncoderLayer)
from .vision import get_model

__all__ = ["vision", "get_model", "BERTModel", "BERTForPretrain", "get_bert",
           "GPT2Model", "get_gpt2", "gpt2_lm_loss", "MoELayer",
           "MoETransformerBlock", "pop_aux_losses", "StackedGPT2Model",
           "get_stacked_gpt2", "MultiHeadAttention", "PositionwiseFFN",
           "TransformerBlock", "TransformerEncoderLayer",
           "TransformerNMT", "TransformerDecoderBlock", "get_nmt",
           "nmt_loss", "NemotronHModel", "get_nemotron_h", "Qwen3NextModel",
           "get_qwen3_next", "GraniteHybridModel", "get_granite_hybrid",
           "Phi4FlashModel", "get_phi4_flash", "MellumModel", "get_mellum",
           "OuroModel", "get_ouro", "DeepseekV3Model", "get_deepseek_v3"]
