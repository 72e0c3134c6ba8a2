"""The FLOP function against hand-computed values at the published shapes."""
from benchmark import flops, loader

BERT = loader.read_json(loader.HERE + "/configs/bert_base_mlm.json")


def test_bert_base_train_flops_per_token():
    # one layer, forward, per token at 512: qkv 2*768*2304 + out 2*768*768
    # + ffn 4*768*3072 + attention 4*512*768
    layer = 3538944 + 1179648 + 9437184 + 1572864
    fwd = 12 * layer + 2 * 768 * 768 + 2 * 768 * 30522
    assert layer == 15728640
    assert flops.bert_mlm_train_flops_per_token(BERT, 512) == 3 * fwd
    assert 3 * fwd == 710_415_360
