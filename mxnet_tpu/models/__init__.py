"""Model families beyond the vision zoo (BASELINE.json configs:
BERT-base, Transformer-base MT, Llama; Falcon-H1, a state-space mixer
beside attention in every block; vision lives in
``gluon.model_zoo.vision``)."""
from . import bert, falcon_h1, llama, transformer
from .bert import (BERTClassifier, BERTEncoder, BERTForPretrain, BERTModel,
                   get_bert_model)
from .falcon_h1 import FalconH1Model
from .llama import LlamaModel, get_llama, llama_sharding_rules
from .transformer import (MultiHeadAttention, PositionwiseFFN, Transformer,
                          TransformerDecoderCell, TransformerEncoderCell,
                          transformer_sharding_rules)
