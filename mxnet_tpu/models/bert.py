"""BERT (BASELINE config 3 flagship: BERT-base MLM pretraining).

Reference parity: GluonNLP bert.py (BERTModel/BERTEncoder + MLM head, tied
embedding decoder).  Built from mxnet_tpu.models.transformer HybridBlocks.

Distributed story (SURVEY §2.3): data parallel over the 'dp' mesh axis and
tensor parallel over 'tp' via the sharding rules below — the Megatron
column/row split of qkv/proj/ffn weights, with GSPMD inserting the
all-reduces on ICI.
"""
from __future__ import annotations

import jax
import numpy as np

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..parallel.sharding import ShardingRules
from .transformer import PositionalEmbedding, TransformerEncoder

__all__ = ["BERTModel", "BERTForMLM", "bert_base", "bert_small",
           "bert_sharding_rules"]


class BERTModel(HybridBlock):
    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512, type_vocab=2,
                 dropout=0.1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(type_vocab, units,
                                                 prefix="type_embed_")
            self.pos_embed = PositionalEmbedding(max_length, units,
                                                 prefix="pos_embed_")
            self.embed_ln = nn.LayerNorm(in_channels=units, prefix="embed_ln_")
            self.embed_drop = nn.Dropout(dropout)
            self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                              num_heads, dropout,
                                              activation="gelu",
                                              prefix="encoder_")
            self.pooler = nn.Dense(units, flatten=False, activation="tanh",
                                   prefix="pooler_")

    def hybrid_forward(self, F, inputs, token_types=None, valid_length=None):
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.pos_embed(x)
        x = self.embed_drop(self.embed_ln(x))
        mask = None
        if valid_length is not None:
            T = inputs.shape[1]
            steps = F.arange(0, T, ctx=inputs.context).reshape(1, -1)
            keep = F.broadcast_lesser(steps, valid_length.reshape(-1, 1))
            mask = F.batch_dot(keep.expand_dims(-1), keep.expand_dims(1))
        out = self.encoder(x, mask)
        pooled = self.pooler(F.slice_axis(out, axis=1, begin=0, end=1)
                             .reshape(0, -1))
        return out, pooled


class BERTForMLM(HybridBlock):
    """BERT with masked-LM head (decoder tied to word embedding would need
    shared-parameter plumbing; an independent decoder matches GluonNLP's
    non-tied option and keeps the vocab projection 'tp'-shardable)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512, dropout=0.1,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab_size = vocab_size
        with self.name_scope():
            self.bert = BERTModel(vocab_size, units, hidden_size, num_layers,
                                  num_heads, max_length, dropout=dropout,
                                  prefix="bert_")
            self.mlm_dense = nn.Dense(units, flatten=False, activation=None,
                                      prefix="mlm_dense_")
            self.mlm_ln = nn.LayerNorm(in_channels=units, prefix="mlm_ln_")
            self.decoder = nn.Dense(vocab_size, flatten=False,
                                    prefix="decoder_")

    def hybrid_forward(self, F, inputs, token_types=None, valid_length=None):
        seq, _ = self.bert(inputs, token_types, valid_length)
        with jax.named_scope("mx_head"):
            h = self.mlm_ln(F.LeakyReLU(self.mlm_dense(seq), act_type="gelu"))
            return self.decoder(h)


def bert_sharding_rules() -> ShardingRules:
    """Megatron-style TP rules over the 'tp' mesh axis.

    Dense weights are (out, in): axis-0 split = column parallel, axis-1 =
    row parallel.  qkv and ffn1 are column-parallel; proj and ffn2 are
    row-parallel; embeddings and the MLM decoder split the vocab axis.
    """
    return ShardingRules([
        (r".*qkv_weight$", ("tp", None)),
        (r".*qkv_bias$", ("tp",)),
        (r".*proj_weight$", (None, "tp")),
        (r".*ffn1_weight$", ("tp", None)),
        (r".*ffn1_bias$", ("tp",)),
        (r".*ffn2_weight$", (None, "tp")),
        (r".*word_embed_weight$", ("tp", None)),
        (r".*decoder_weight$", ("tp", None)),
        (r".*decoder_bias$", ("tp",)),
    ])


def bert_base(vocab_size=30522, **kwargs) -> BERTForMLM:
    return BERTForMLM(vocab_size=vocab_size, units=768, hidden_size=3072,
                      num_layers=12, num_heads=12, **kwargs)


def bert_small(vocab_size=512, units=64, hidden_size=128, num_layers=2,
               num_heads=4, max_length=64, **kwargs) -> BERTForMLM:
    """Tiny config for dryruns and tests."""
    return BERTForMLM(vocab_size=vocab_size, units=units,
                      hidden_size=hidden_size, num_layers=num_layers,
                      num_heads=num_heads, max_length=max_length, **kwargs)
