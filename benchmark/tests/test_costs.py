"""``costs.py`` against a count made by hand."""
import costs
import harness


def test_bert_base_flops_per_token_matches_the_hand_count():
    cfg = harness.load_json(harness.HERE, "configs",
                            "bert-base-pretrain.json")
    # one encoder layer, multiply-adds per token:
    #   q, k, v, out projections  4 * 768 * 768   = 2,359,296
    #   FFN in and out            2 * 768 * 3072  = 4,718,592
    #   scores and context        2 * 512 * 768   =   786,432
    layer = 2_359_296 + 4_718_592 + 786_432
    #   MLM transform 768 * 768 = 589,824; vocabulary 768 * 30522 =
    #   23,440,896; pooler + NSP once a row of 512 tokens
    head = 589_824 + 23_440_896 + (768 * 768 + 2 * 768) / 512
    forward = 2 * (12 * layer + head)          # 2 FLOPs a multiply-add
    want = 3 * forward                         # backward is twice forward
    got = costs.bert_pretrain_flops_per_token(cfg)
    assert abs(got - want) < 1.0
    assert 7.0e8 < got < 7.2e8                 # about 0.71 GFLOP a token
