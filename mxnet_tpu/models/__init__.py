"""Flagship model families built on gluon (transformer/BERT here;
CNN zoo in gluon.model_zoo.vision)."""
from . import transformer
from . import bert
from . import ssd
from . import faster_rcnn
from . import bert_pp
from . import nemotron_h
from .bert import BERTModel, BERTForMLM, bert_base, bert_small
from .bert_pp import (BERTForMLMPipelined, StackedTransformerEncoder,
                      bert_pp_small, bert_pp_sharding_rules)
from .faster_rcnn import (FasterRCNN, FasterRCNNTrainLoss,
                          faster_rcnn_small)
from .nemotron_h import NemotronHModel
from .ssd import SSD, SSDTrainLoss, ssd_300
from .transformer import (TransformerEncoder, MultiHeadAttention,
                          Transformer, TransformerDecoder, transformer_base,
                          transformer_big, label_smoothed_ce)
